"""Device-plane fault boundary: typed errors, injection, classification.

Every accelerator dispatch the resilience plane supervises (kernel-server
requests, resumable mesh-analytics chunks, the kernel server's probe)
calls :func:`device_fault_point` first. Unarmed it costs one module-flag
read per point; armed (via ``utils/faultinject``) it turns into the four
canonical device failures:

    device.call   XlaRuntimeError — a dispatch/compile failure. Raised as
                  the REAL jaxlib ``XlaRuntimeError`` when jaxlib is
                  importable, so production handlers exercise exactly the
                  type they would see from a live device.
    device.oom    RESOURCE_EXHAUSTED — the HBM OOM the admission guard
                  exists to prevent; message carries the XLA status code
                  text so string-based classifiers treat it like the
                  real thing.
    device.hang   armed with ``delay:<sec>`` — the dispatch stalls past
                  its deadline (fire() sleeps; no exception). The wedge
                  class the kernel-server supervision loop contains.
    device.lost   the backend is gone. Armed ``raise`` it is an
                  in-process :class:`DeviceLostError` (resumable loops
                  re-place inputs and resume from their checkpoint);
                  armed ``kill`` it takes down the whole process — the
                  resident kernel-server daemon case, which the client
                  supervisor answers by restarting the server.

:func:`classify_device_error` is the shared classification: it maps real AND
injected device exceptions onto {"oom", "device_lost", "device_error"}
so the kernel server, the checkpoint runner and the probe op all
report the same typed outcome for the same failure.
"""

from __future__ import annotations

import logging
import os
import sys

from . import faultinject as FI

log = logging.getLogger(__name__)


class DeviceFaultError(RuntimeError):
    """Base for injected device-plane failures (in-process stand-ins for
    the XLA runtime errors a real device raises)."""


class DeviceLostError(DeviceFaultError):
    """The backend for this process is gone (chip reset, runtime died).

    Unlike a per-call failure, resident device buffers and compiled
    executables must be assumed invalid: recovery means re-placing
    inputs and resuming from host-side checkpoint state.
    """


class DeviceOomError(DeviceFaultError):
    """Device memory exhausted (RESOURCE_EXHAUSTED)."""


class ChipOwnedError(RuntimeError):
    """This process has initialised a TPU backend, so it owns the chip.

    A chip belongs to one process at a time: a child that needs it
    would fail or hang on the chip's lock, so the parent refuses to
    start one and says so instead."""


def process_holds_tpu() -> bool:
    """True once this process has initialised a TPU backend. Only reads
    state: a process that never touched jax (or only imported it) does
    not get a backend initialised by asking."""
    jax = sys.modules.get("jax")
    if jax is None:
        return False
    from jax._src import xla_bridge
    if not xla_bridge.backends_are_initialized():
        return False
    return jax.default_backend() == "tpu"


def refuse_chip_child(what: str) -> None:
    """Raise :class:`ChipOwnedError` when this process owns the chip and
    is about to start `what`, a child that would reach for the same
    chip."""
    if process_holds_tpu():
        raise ChipOwnedError(
            f"refusing to {what}: this process (pid {os.getpid()}) has "
            "initialised the TPU backend and owns the chip, and a chip "
            "belongs to one process at a time. Start the child from a "
            "process that has not touched the device, or run the work "
            "in this process.")


def _xla_error_type():
    """The real XlaRuntimeError when jaxlib is importable, else None."""
    try:
        from jaxlib.xla_extension import XlaRuntimeError
        return XlaRuntimeError
    except Exception as e:  # noqa: BLE001 — jaxlib layout varies
        log.debug("no importable XlaRuntimeError (%s); falling back to "
                  "DeviceFaultError", e)
        return None


def make_device_call_error(detail: str) -> Exception:
    """An injected dispatch failure, as the real XlaRuntimeError type
    when available so handlers catch exactly the production class."""
    xla_err = _xla_error_type()
    msg = f"INTERNAL: injected device failure: {detail}"
    if xla_err is not None:
        try:
            return xla_err(msg)
        except Exception as e:  # noqa: BLE001 — not constructible here
            log.debug("XlaRuntimeError not constructible (%s); using "
                      "DeviceFaultError", e)
    return DeviceFaultError(msg)


def device_fault_point() -> None:
    """The device dispatch hook. Fires the whole ``device.*`` family in
    canonical order (hang → lost → oom → call) so one call site covers
    every armed device fault; each point keeps its own hit counter, so
    seeded schedules address the N-th dispatch of a specific kind."""
    FI.fire("device.hang")          # delay specs sleep here, then continue
    try:
        FI.fire("device.lost")
    except FI.FaultInjected as e:   # (the "kill" action never returns)
        raise DeviceLostError(
            f"UNAVAILABLE: device backend lost: {e}") from e
    try:
        FI.fire("device.oom")
    except FI.FaultInjected as e:
        raise DeviceOomError(
            "RESOURCE_EXHAUSTED: injected out-of-memory allocating "
            f"device buffer: {e}") from e
    try:
        FI.fire("device.call")
    except FI.FaultInjected as e:
        raise make_device_call_error(str(e)) from e


#: substrings XLA status messages carry for each failure class (the
#: jaxlib error type is one opaque XlaRuntimeError; the status code
#: prefix in the message is the only discriminator the runtime gives us)
_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Out of memory", "OOM")
_LOST_MARKERS = ("UNAVAILABLE", "device lost", "DATA_LOSS",
                 "backend lost", "failed to connect")


def classify_device_error(exc: BaseException) -> str | None:
    """Map an exception to a typed device outcome, or None when it is
    not a device-plane failure (caller re-raises those unchanged).

    Returns one of ``"oom"``, ``"device_lost"``, ``"device_error"``.
    """
    if isinstance(exc, DeviceOomError):
        return "oom"
    if isinstance(exc, DeviceLostError):
        return "device_lost"
    if isinstance(exc, DeviceFaultError):
        return "device_error"
    xla_err = _xla_error_type()
    is_xla = xla_err is not None and isinstance(exc, xla_err)
    # jax raises XlaRuntimeError for every device-side failure; the
    # status code rides the message text
    if is_xla:
        text = str(exc)
        if any(m in text for m in _OOM_MARKERS):
            return "oom"
        if any(m in text for m in _LOST_MARKERS):
            return "device_lost"
        return "device_error"
    return None
