"""MG005 fixture stat registry (r14, mgstat): one wired exact name, one
wired family, one dead name, one dead family, one duplicate; the emit
sites live in user.py."""

STAT_NAMES = (
    "wired.stat",       # emitted below in user.py
    "wired.family.*",   # dynamic family, emitted in user.py
    "dead.stat",        # MG005: declared but never emitted
    "dead.family.*",    # MG005: family with no dynamic site
    "dup.stat",         # emitted once ...
    "dup.stat",         # ... MG005: but declared twice
    "span.*",           # emitted by trace.py's PHASES keys: silent
)


class _Metrics:
    def increment(self, name, delta=1):
        pass

    def set_gauge(self, name, value):
        pass

    def observe(self, name, value):
        pass


global_metrics = _Metrics()
