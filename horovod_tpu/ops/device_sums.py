"""Sums a model makes on the device inside a compiled train step.

Some of what a model should count exists only on the device: how many
of a routed layer's assignments fell on the experts it holds depends on
the batch.  Fetching such a number every step would make every step
wait for the host.  Instead the sums ride the state the step already
threads: a loss function declares their names (``loss_fn.device_sums``,
which ``models.make_fused_lm_loss`` takes from its model), the step's
``init_state`` adds ``state["device_sums"]``, a 64-bit accumulator a
name, and the step program adds to them what the model handed ``add``
while the step traced its loss.  The program also returns a copy of the
new sums that no later call donates; the step publishes it (a
reference, no transfer), and a READ of the process's metric registry
(``telemetry.counter_total``, ``metrics()``, the exporters) fetches the
newest copy and advances the counters of those names by what was
added since the last read.  So a step never waits for the host, and a
reader waits for the newest step.

Outside a compiled step's trace ``add`` does nothing.
"""

import contextlib
import threading

import jax
import jax.numpy as jnp
import numpy as np

_TRACING = threading.local()
STATE_KEY = "device_sums"
#: a sum of fractions (a probability mass over tokens) is kept in steps
#: of 2**-FRACTION_BITS: a step call may add up to 2**(31 -
#: FRACTION_BITS) to it over all ranks, and rounds by at most half a
#: step
FRACTION_BITS = 8
_FRACTIONS = set()     # the names ``add_fraction`` was handed


def declared(loss_fn):
    """The names ``loss_fn`` says it sums on the device."""
    return tuple(getattr(loss_fn, STATE_KEY, ()))


def add(name, value):
    """Add ``value`` (a non-negative int32 scalar of the loss's own
    trace: not one from inside a scan or a remat body) to the sum
    ``name`` of the step that is tracing on this thread."""
    found = getattr(_TRACING, "sums", None)
    if found is not None:
        found[name] = found.get(name, 0) + value


def add_fraction(name, value):
    """``add`` for a non-negative float scalar ``value``: the sum
    ``name`` is kept in fixed point on the device and read as a float
    counter."""
    _FRACTIONS.add(name)
    add(name, jnp.round(value * (1 << FRACTION_BITS)).astype(jnp.int32))


@contextlib.contextmanager
def collecting():
    """Opened by a step around the trace of its loss, inside the
    function it differentiates; yields {name: what was added}."""
    outer = getattr(_TRACING, "sums", None)
    _TRACING.sums = found = {}
    try:
        yield found
    finally:
        _TRACING.sums = outer


def zeros(names):
    """The accumulators a state starts from: (high, low) uint32 words
    a name (a chip process has no 64-bit types)."""
    return {name: jnp.zeros((2,), jnp.uint32) for name in names}


def accumulate(total, value):
    """``total`` (high, low) plus the non-negative int32 ``value``, the
    carry taken into the high word."""
    low = total[1] + value.astype(jnp.uint32)
    return jnp.stack([total[0] + (low < total[1]).astype(jnp.uint32), low])


def publish(source, names, newest):
    """Note ``newest`` (the accumulators of ``names``, stacked, on the
    device) as the sums of ``source`` (any hashable: a step's program)
    in the process's current metric registry; transfers nothing."""
    from .. import telemetry

    reg = telemetry.registry()
    sources = getattr(reg, "_device_sums", None)
    if sources is None:
        sources = reg._device_sums = {}
        reg.on_read(lambda: _fold(reg))
    sources.setdefault(source, {"seen": {}, "names": names})[
        "newest"] = newest


def _fold(reg):
    """Fetch every source's newest sums and advance the counters."""
    for record in list(reg._device_sums.values()):
        newest = np.asarray(jax.device_get(record["newest"])).tolist()
        for name, (high, low) in zip(record["names"], newest):
            value, seen = (high << 32) | low, record["seen"].get(name, 0)
            record["seen"][name] = value
            # a state that started again from zero counts from there
            reg.counter(name, _HELP).labels().inc(
                (value - seen if value >= seen else value)
                / (1 << FRACTION_BITS if name in _FRACTIONS else 1))


_HELP = ("Summed on the device inside the compiled train step "
         "(ops/device_sums.py)")
