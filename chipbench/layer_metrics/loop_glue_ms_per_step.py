"""Of ``loop_ms_per_step``, the time under none of ``attn``, ``mlp`` and
the norms (``ln_*``): what running one stack several times costs beside
the layers' own work (the two scans' slices and stacking, the residual
adds, and the sum over the passes of the stack's float32 weight
gradient), the mean over the chips (``chipbench/scope_time.py``)."""

from chipbench import scope_join, scope_time

COUNTERS = []
# ``scope_time.component`` for attn, mlp and any norm (``ln_<name>``)
_LAYER_WORK = r"(?:^|[/(])(?:attn|mlp|ln_\w+)(?=[/)]|$)"


def read(ctx):
    report = scope_join.report_of(ctx) if ctx.get("trace") else None
    if report is None:
        return None
    loop = scope_time.component("loop")
    found = scope_time.split_ms(
        ctx["trace"], report["scopes"], ctx["trace_steps"],
        {"loop": loop, "layers": loop + ".*" + _LAYER_WORK})
    return found["loop"] - found["layers"] if found["loop"] > 0 else None
