"""MG005 fixture span registry (r13, mgtrace): one wired name, one
dead registration; the open sites live in user.py. The phase mark
rides it: one phase that is a declared span, one that is not."""

SPAN_NAMES = (
    "wired.span",       # opened below in user.py
    "dead.span",        # MG005: declared but never opened
)


PHASES = {
    "wired.span": (),       # a declared span: silent
    "ghost.phase": (),      # MG005: a phase no span name declares
}


def span(name, **attrs):
    return None


def record_span(name, start_wall, duration_s, **attrs):
    return None
