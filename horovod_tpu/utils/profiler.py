"""Device-side profiling: jax profiler (XPlane/Perfetto) integration.

SURVEY §5.1: the reference traces with (a) the host-side Timeline and
(b) NVTX ranges around every user-facing op for nsight
(``nvtx_op_range.{h,cc}``, started in EnqueueTensorAllreduces).  On
TPU the device-side tracer is the jax profiler — its traces carry XLA
op timelines, HBM usage, and ICI collective activity.  This module is
the thin glue: start/stop the trace programmatically (reference
start_timeline/stop_timeline shape) and annotate host-side phases so
they appear as named ranges alongside device activity (the NVTX role).

``annotate`` always emits a ``TraceAnnotation`` — jax's TraceMe is a
nanosecond-level no-op while no profiler is attached, and this way
ranges also show up in traces started elsewhere (TensorBoard's
on-demand remote profiling, a direct ``jax.profiler.trace``).
"""

import contextlib
import threading
import time

_lock = threading.Lock()
_active = False


def start_profile(logdir: str):
    """Begin an XPlane trace into ``logdir`` (view with TensorBoard's
    profile plugin or Perfetto).  Reference analogue:
    horovod_start_timeline (operations.cc:1077).  Raises if a trace
    started through this module is already running."""
    global _active
    import jax

    with _lock:
        if _active:
            raise RuntimeError(
                "a profile is already active; stop_profile() first "
                "(jax supports one trace at a time)")
        jax.profiler.start_trace(logdir)
        _active = True


def stop_profile():
    global _active
    import jax

    with _lock:
        if not _active:
            return
        _active = False
        jax.profiler.stop_trace()


class annotate:
    """Named range in the profile (the reference's NvtxOpRange).
    Near-zero overhead when no profiler is attached.

    The one way this package opens a host span.  ``seconds``, a
    counter child of the telemetry registry, also gets the elapsed
    ``perf_counter`` seconds, so that a phase is on the device trace's
    clock when someone profiles and in the always-on counters when
    no one does; ``beside`` is one more context manager entered inside
    the range (the engine timeline's span on the eager path)."""

    __slots__ = ("_range", "_seconds", "_beside", "_t0")

    def __init__(self, name: str, seconds=None, beside=None):
        import jax

        self._range = jax.profiler.TraceAnnotation(name)
        self._seconds = seconds
        self._beside = beside

    def __enter__(self):
        self._range.__enter__()
        if self._beside is not None:
            self._beside.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        elapsed = time.perf_counter() - self._t0
        if self._beside is not None:
            self._beside.__exit__(*exc)
        self._range.__exit__(*exc)
        if self._seconds is not None:
            self._seconds.inc(elapsed)
        return False


@contextlib.contextmanager
def profile(logdir: str):
    """Trace a scoped region: ``with profile('/tmp/trace'): step()``."""
    start_profile(logdir)
    try:
        yield
    finally:
        stop_profile()
