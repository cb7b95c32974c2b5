"""What can be known about the chip without one.

* The TPU compiler is installed here and compiles for a chip that is
  described, not attached: every Pallas kernel of the main path at
  lm436m and the cells' widths must be taken by it as a Mosaic
  ``tpu_custom_call``.  Interpret mode cannot show this — the int4
  codec passed every interpret-mode test while the compiler refused
  it.  Nothing runs, so these say nothing about results or times.
* ``chip_smoke.py``'s control flow, the compile-cache placement and
  the one-process-per-chip rules, on the CPU.
"""

import json
import os
import re
import subprocess
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")   # or libtpu logs to /tmp

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


@pytest.fixture(scope="module")
def chips():
    """The four described TPU v5e chips of a 2x2 host, the persistent
    compile cache off around the module: a compile for a described
    device is written to the cache but cannot be read back without a
    chip, and the next one would warn."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"cannot describe a v5e:2x2 topology: {exc}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield list(topo.devices)
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def chip(chips):
    return chips[0]


# lm436m attention operands (chip_smoke.py HEADLINE, B5)
_QKV = [((5, 2048, 8, 128), jnp.bfloat16)] * 3
# the long-context windowed shape: S8192, W1024
_QKV_LONG = [((1, 8192, 8, 128), jnp.bfloat16)] * 3
# the benchmark's s8k cells (chipbench/: Mistral-7B and Trinity-Mini at
# 2 x 8192 tokens, 32 heads of 128): full, Mistral's window, Trinity's
_QKV_S8K = [((2, 8192, 32, 128), jnp.bfloat16)] * 3
# ... SmallThinker's 28 query heads (7 to each of 4 kv heads, expanded
# as the model hands them over: 56 rows of heads, no power of two)
_QKV_S8K_H28 = [((2, 8192, 28, 128), jnp.bfloat16)] * 3
# ... Granite-4.0-H's one attention layer: 1 x 8192 tokens, 32 heads of
# 64 (half a lane tile in the transposed score layout)
_QKV_S8K_D64 = [((1, 8192, 32, 64), jnp.bfloat16)] * 3
# ... Kimi-Linear's one latent-attention layer: 2 x 8192 tokens, 32
# heads whose queries and keys are 192 wide (the values go in filled up
# to 192): k and v whole pass the compiler's default 16 MiB of scoped
# VMEM, so both kernels ask for what their shapes need
_QKV_S8K_D192 = [((2, 8192, 32, 192), jnp.bfloat16)] * 3
# ... and its s4k cells (4 x 4096 tokens; the 4096 window does not bind)
_QKV_S4K = [((4, 4096, 32, 128), jnp.bfloat16)] * 3


def _ssd_shapes(seq, dtype=jnp.bfloat16, heads=64, groups=1):
    """A state-space layer's scan (x, dt, A, B, C and the D skip) at
    Granite-4.0-H's widths: heads of 64, groups of 128 states."""
    return [((1, seq, heads, 64), dtype), ((1, seq, heads), jnp.float32),
            ((heads,), jnp.float32), ((1, seq, groups, 128), dtype),
            ((1, seq, groups, 128), dtype), ((heads,), jnp.float32)]


_N = 1 << 20              # quantize codecs: 4096 scale blocks of 256


def _flash(**kw):
    from horovod_tpu.ops.pallas_kernels import flash_attention

    def fwd(q, k, v):
        return flash_attention(q, k, v, interpret=False, **kw)
    return fwd


def _ssd(*args):
    from horovod_tpu.ops.ssd_kernels import ssd_scan

    return ssd_scan(*args, chunk=256, interpret=False)[0]


def _paged(pool, heads, width, window=None, slots=32):
    """Decode attention over the paged cache (ops/paged_kernels.py):
    one layer's call inside the tick, on flat pools of 1,024 blocks."""
    from horovod_tpu.ops.paged_kernels import paged_decode_attention

    def fwd(q, k_pool, v_pool, tables, pos, base):
        return paged_decode_attention(q, k_pool, v_pool, tables, pos,
                                      base, window=window,
                                      interpret=False)
    flat = ((1024,) + pool, jnp.bfloat16)
    return fwd, [((slots, heads, pool[-1]), jnp.bfloat16), flat, flat,
                 ((slots, width), jnp.int32), ((slots,), jnp.int32),
                 ((), jnp.int32)], 1


def _kernel_names(text):
    """The names of a compiled program's Mosaic kernels, one a call."""
    return re.findall(
        r"%(\w+?)(?:\.\d+)? = [^\n]*custom_call_target=\"tpu_custom_call\"",
        text)


def _grad_of_sum(fn, n_args):
    return jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32)),
                    argnums=tuple(range(n_args)))


def _kernel_cases():
    from horovod_tpu.ops import pallas_kernels as pk

    blocks = _N // 256
    return {
        "flash_fwd": (_flash(), _QKV, 1),
        # the forward alone at the four shapes the cells run
        "flash_fwd_s4k": (_flash(), _QKV_S4K, 1),
        "flash_fwd_s8k": (_flash(), _QKV_S8K, 1),
        "flash_fwd_s8k_w4096": (_flash(window=4096), _QKV_S8K, 1),
        "flash_fwd_s8k_w2048": (_flash(window=2048), _QKV_S8K, 1),
        "flash_fwd_s8k_h28": (_flash(), _QKV_S8K_H28, 1),
        "flash_fwd_s8k_h28_w4096": (_flash(window=4096), _QKV_S8K_H28, 1),
        "flash_fwd_s8k_d64": (_flash(), _QKV_S8K_D64, 1),
        "flash_bwd_s8k_d64": (_grad_of_sum(_flash(), 3), _QKV_S8K_D64, 2),
        "flash_fwd_s8k_d192": (_flash(), _QKV_S8K_D192, 1),
        "flash_bwd_s8k_d192": (_grad_of_sum(_flash(), 3), _QKV_S8K_D192, 2),
        "flash_bwd": (_grad_of_sum(_flash(), 3), _QKV, 2),
        "flash_window_bwd": (
            _grad_of_sum(_flash(window=1024), 3), _QKV_LONG, 2),
        # where an over-asked VMEM shows without a chip: the backward
        # keeps k, v, dk, dv whole and two float32 accumulators
        "flash_bwd_s8k": (_grad_of_sum(_flash(), 3), _QKV_S8K, 2),
        "flash_bwd_s8k_w4096": (
            _grad_of_sum(_flash(window=4096), 3), _QKV_S8K, 2),
        "flash_bwd_s8k_w2048": (
            _grad_of_sum(_flash(window=2048), 3), _QKV_S8K, 2),
        "flash_bwd_s8k_h28": (_grad_of_sum(_flash(), 3), _QKV_S8K_H28, 2),
        "flash_bwd_s8k_h28_w4096": (
            _grad_of_sum(_flash(window=4096), 3), _QKV_S8K_H28, 2),
        # the selective scan's pair, the forward sweep alone and with
        # the reverse sweep (the gradient of all six operands), at
        # Granite's cell (1 x 8192 tokens), at its rehearsal (one row
        # of 128: ONE chunk of 128), and at what else ``kernel_takes``
        # says yes to: float32 activations, two groups of B and C
        "ssd_fwd_s8k": (_ssd, _ssd_shapes(8192), 1),
        "ssd_bwd_s8k": (_grad_of_sum(_ssd, 6), _ssd_shapes(8192), 2),
        "ssd_fwd_s128": (_ssd, _ssd_shapes(128), 1),
        "ssd_bwd_s128": (_grad_of_sum(_ssd, 6), _ssd_shapes(128), 2),
        "ssd_bwd_s8k_f32": (
            _grad_of_sum(_ssd, 6), _ssd_shapes(8192, jnp.float32), 2),
        "ssd_bwd_s1k_g2": (
            _grad_of_sum(_ssd, 6), _ssd_shapes(1024, heads=16, groups=2), 2),
        # the served cell's decode attention (Mistral's 32 heads over 8
        # KV heads of 128, blocks of 16, its window) at its widest and
        # its narrowest table, and what else ``kernel_takes`` says yes
        # to: 16 KV heads of 256, blocks of two tokens (one tile of rows)
        "paged_decode": _paged((16, 8, 128), 32, 256, window=4096),
        "paged_decode_nb1": _paged((16, 8, 128), 32, 1, window=4096),
        "paged_decode_kv16_d256": _paged((16, 16, 256), 64, 32),
        "paged_decode_bt2": _paged((2, 8, 128), 16, 64, window=48),
        "quantize_int8": (
            lambda x: pk.quantize_blockwise(x, interpret=False),
            [((_N,), jnp.float32)], 1),
        "dequantize_int8": (
            lambda q, s: pk.dequantize_blockwise(q, s, _N,
                                                 interpret=False),
            [((_N,), jnp.int8), ((blocks,), jnp.float32)], 1),
        "quantize_int4": (
            lambda x: pk.quantize_blockwise_int4(x, interpret=False),
            [((_N,), jnp.float32)], 1),
        "dequantize_int4": (
            lambda q, s: pk.dequantize_blockwise_int4(q, s, _N,
                                                      interpret=False),
            [((_N // 2,), jnp.uint8), ((blocks,), jnp.float32)], 1),
        "fused_scale_cast": (
            lambda x: pk.fused_scale_cast(x, 0.5, jnp.bfloat16,
                                          interpret=False),
            [((_N,), jnp.float32)], 1),
    }


def _lm436m_step_case():
    """The framework's own one-chip lm436m step program
    (ops/compiled.py), handed the described device and eval_shape
    shapes — the stacked single-rank program chip_smoke.py runs."""
    import functools

    import chip_smoke
    import optax

    from horovod_tpu.models import TransformerLM
    from horovod_tpu.ops.compiled import make_compiled_train_step
    from horovod_tpu.ops.pallas_kernels import flash_attention
    from horovod_tpu.ops.xla_ops import MeshExecutor

    cfg, _ = chip_smoke.build(chip_smoke.HEADLINE_BATCH)
    tokens = ((chip_smoke.HEADLINE_BATCH, cfg.max_seq_len), jnp.int32)
    _, loss_fn = chip_smoke.model_and_loss(cfg, functools.partial(
        flash_attention, interpret=False))
    optimizer = optax.adamw(1e-3)
    step = make_compiled_train_step(loss_fn, optimizer)
    params = jax.eval_shape(
        lambda t: TransformerLM(cfg).init(jax.random.PRNGKey(0),
                                          t)["params"],
        jax.ShapeDtypeStruct(*tokens))
    state = jax.eval_shape(
        lambda p: {"params": p, "opt_state": optimizer.init(p)}, params)

    def program(device):
        return step._build(MeshExecutor([device], 1))

    # (R=1, B, S) batch rows: the stacked single-device layout
    return program, state, ((1,) + tokens[0], tokens[1])


def _lowered_cell_step(monkeypatch, cell, **config_changes):
    """A benchmark cell's step program lowered for the described chip by
    ``tools/described_step.py``, a command: it reads the checkout it
    stands in and sets its process up for the chip's compiler, which a
    test puts back."""
    from horovod_tpu.ops import pallas_kernels
    from tools import described_step

    monkeypatch.chdir(REPO)
    monkeypatch.setattr(pallas_kernels, "default_interpret",
                        pallas_kernels.default_interpret)
    limit = jax.config.jax_traceback_in_locations_limit
    try:
        return described_step.lowered_step(cell, **config_changes)
    finally:
        jax.config.update("jax_traceback_in_locations_limit", limit)


@pytest.mark.slow
@pytest.mark.parametrize("policy, fits", [("full", True),
                                          ("dots_flash", False)])
def test_looped_cell_fits_under_full_remat_only(chip, policy, fits,
                                                monkeypatch):
    """The benchmark's looped cell (Ouro-2.6B: 8 layers, 4 passes, 1 x
    4096 tokens, 9.8 GB of state) as the chip's compiler sees its step:
    it takes ``full`` remat and refuses ``dots_flash``, which every
    other LM cell runs under, for HBM (PERF.md section 6, PR 32).
    ``full`` keeps the flash kernels' outputs by name, so the replay of
    a pass runs no kernel again: one forward and one backward call site
    a pass (8 + 4 before the names, PR 48), for 0.43 GB more."""
    lowered = _lowered_cell_step(monkeypatch, "ouro-2.6b-s4k-1chip",
                                 remat_policy=policy)
    with jax.enable_x64(False):
        if not fits:
            with pytest.raises(Exception, match="Ran out of memory"):
                lowered.compile()
            return
        compiled = lowered.compile()
    kernels = _kernel_names(compiled.as_text())
    assert sorted(set(kernels)) == ["flash_dkv", "flash_fwd"]
    assert kernels.count("flash_fwd") == kernels.count("flash_dkv") == 4
    # the program's own account over-states what the chip holds (20.9 GB
    # for 15.5 held, PERF.md section 5), so the limit is the account of
    # the step before the names (7.349 GB of arguments + 13.515 of
    # temporaries) and 0.6 GB, not the chip's size
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        < 7.349e9 + 13.515e9 + 0.6e9


# HBM a v5e's runtime lets a program use (the compiler's own limit is
# 15.75 GiB = 16.9 GB)
_CHIP_HBM_BYTES = 16.9e9


@pytest.mark.slow
@pytest.mark.parametrize("cell", ["smallthinker-21b-s8k-1chip",
                                  "trinity-mini-s8k-1chip"])
def test_routed_cells_run_no_grouped_product_twice_and_fit(chip, cell,
                                                           monkeypatch):
    """The two routed cells' steps as the chip's compiler sees them:
    they compile (a step over the chip's memory is refused), the
    program's own account of its memory stays under the chip's, and
    outside the loops over further passes each of the 4 routed layers
    holds the forward's three grouped products and the backward's six
    gradients: the first pass keeps its gate and up products, and
    Trinity's remat replay, which needs the layer's output for the norm
    after it, finds that kept too.  Before: 3 + 9 a layer, Trinity's
    three replayed among the 9."""
    from horovod_tpu.telemetry.programs import program_tables

    lowered = _lowered_cell_step(monkeypatch, cell)
    with jax.enable_x64(False):
        compiled = lowered.compile()
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes \
        + memory.generated_code_size_in_bytes < _CHIP_HBM_BYTES
    # a layer's passes are a loop in a loop: ``sequential_vmap``'s over
    # the one rank, and inside it the loop over FURTHER passes
    further = re.compile(r"/moe/while/body/closed_call/while(/|$)")
    paths = [path for name, path in program_tables(
        compiled.as_text())["scopes"].items()
        if name.startswith("ragged-dot-none")]
    ran = [path for path in paths if not further.search(path)]
    assert len(ran) == 4 * (3 + 6)
    assert not any("rematted_computation" in path for path in paths)
    # each further pass: 3 forward, 3 again + 6 backward
    assert len(paths) - len(ran) == 4 * (3 + 9)


@pytest.mark.slow
def test_hybrid_cell_fits_with_its_scans_in_their_kernels(
        chip, monkeypatch):
    """Granite-4.0-H Micro's cell (nine Mamba-2 layers to one
    attention layer, 798M parameters, 1 x 8,192 tokens, remat that
    keeps the scans' outputs and chunk states alone) compiles for the
    chip with room to spare; its kernels are the attention layer's and
    the scans' and no scan runs twice."""
    with jax.enable_x64(False):
        compiled = _lowered_cell_step(
            monkeypatch, "granite-4.0-h-micro-s8k-1chip").compile()
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == pytest.approx(12 * 797850560, rel=1e-3)
    assert mem.alias_size_in_bytes + mem.temp_size_in_bytes \
        + mem.generated_code_size_in_bytes < 14e9
    text = compiled.as_text()
    # 2 a layer, the attention layer (one forward, one backward) as a
    # mamba layer (``ssd_fwd``, ``ssd_bwd``): the replay needs only what
    # the policy kept by name and runs no kernel again
    assert text.count("tpu_custom_call") == 2 + 2 * 9
    kernels = _kernel_names(text)
    assert sorted(set(kernels)) == ["flash_dkv", "flash_fwd", "ssd_bwd",
                                    "ssd_fwd"]
    assert kernels.count("ssd_fwd") == kernels.count("ssd_bwd") == 9
    assert kernels.count("flash_fwd") == kernels.count("flash_dkv") == 1


@pytest.mark.parametrize("name", [
    "flash_fwd", "flash_fwd_s4k", "flash_fwd_s8k", "flash_fwd_s8k_w4096",
    "flash_fwd_s8k_w2048", "flash_fwd_s8k_h28", "flash_fwd_s8k_h28_w4096",
    "flash_bwd", "flash_window_bwd", "flash_bwd_s8k",
    "flash_bwd_s8k_w4096", "flash_bwd_s8k_w2048", "flash_bwd_s8k_h28",
    "flash_bwd_s8k_h28_w4096", "flash_fwd_s8k_d64", "flash_bwd_s8k_d64",
    "flash_fwd_s8k_d192", "flash_bwd_s8k_d192",
    "ssd_fwd_s8k", "ssd_bwd_s8k", "ssd_fwd_s128", "ssd_bwd_s128",
    "ssd_bwd_s8k_f32", "ssd_bwd_s1k_g2",
    "paged_decode", "paged_decode_nb1", "paged_decode_kv16_d256",
    "paged_decode_bt2",
    "quantize_int8",
    "dequantize_int8", "quantize_int4", "dequantize_int4",
    "fused_scale_cast",
    pytest.param("lm436m_step", marks=pytest.mark.slow)])
def test_chip_compiler_takes(chip, name):
    one_chip = SingleDeviceSharding(chip)

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    if name == "lm436m_step":
        program, state, batch = _lm436m_step_case()
        fn, min_calls = program(chip), 2
        args = [jax.tree.map(lambda s: shaped(s.shape, s.dtype), state),
                shaped(*batch)]
    else:
        fn, shapes, min_calls = _kernel_cases()[name]
        fn, args = jax.jit(fn), [shaped(*s) for s in shapes]
    # the suite runs with 64-bit types on (conftest); a chip process
    # does not, and Mosaic has no 64-bit index
    with jax.enable_x64(False):
        compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    calls = text.count("tpu_custom_call")
    assert calls >= min_calls
    if name.startswith(("flash_", "ssd_")):
        # the forward, and ONE backward kernel: no more, under the
        # names the benchmark's readers know (chipbench/scope_join.py;
        # the scan's are read by their scope, ``mamba`` + ``ssd``)
        assert calls == min_calls
        assert sorted(_kernel_names(text)) == (
            ["flash_dkv", "flash_fwd"][-min_calls:]
            if name.startswith("flash_")
            else ["ssd_bwd", "ssd_fwd"][-min_calls:])
    if name.startswith("paged_"):
        assert _kernel_names(text) == ["paged_decode_attention"]
        # the kernel's view of a block as rows is the pools' own bytes
        assert not re.search(
            r"bf16\[1024,[\d,]+\][^ ]* (copy|reshape|transpose|fusion)\(",
            text)
    if name == "lm436m_step":
        mem = compiled.memory_analysis()
        # donated state in, the same bytes out, and the step's
        # temporaries: what B5 needs of a 16 GB chip
        assert mem.alias_size_in_bytes > 5e9
        assert mem.temp_size_in_bytes < 12.5e9


def test_served_decode_tick_reads_the_pools_in_place(chip):
    """The widest decode program of the served cells (16 layers of
    Mistral's widths, 32 slots, a table of 256 blocks of 16 tokens,
    4,096 blocks a layer) with the kernel form forced through Mosaic
    and the pools donated, as the chip runs it: the two pools aliased,
    no temporary of a view's size, and nothing of a pool's or a gathered
    view's shape but the two in-place scatters inside the loop."""
    from horovod_tpu.models.transformer import TransformerConfig, TransformerLM
    from horovod_tpu.serving.kvcache import PagedKVPrograms

    cfg = TransformerConfig(
        vocab_size=32000, d_model=4096, n_layers=16, n_heads=32,
        n_kv_heads=8, d_ff=14336, max_seq_len=4096,
        attention_window=4096, rope_theta=10000.0, dtype=jnp.bfloat16)
    programs = PagedKVPrograms(cfg, max_slots=32, block_tokens=16,
                               n_blocks=4096, donate=True, interpret=False)
    assert programs.reads_in_place
    one_chip = SingleDeviceSharding(chip)

    def shaped(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda s: shaped(s.shape, cfg.dtype),
        jax.eval_shape(lambda t: TransformerLM(cfg).init(
            jax.random.PRNGKey(0), t)["params"],
            jax.ShapeDtypeStruct((1, 8), jnp.int32)))
    pool = shaped(programs.pool_shape, cfg.dtype)
    width = programs.table_buckets[-1]
    assert width == 256
    with jax.enable_x64(False):
        compiled = programs._decode_program(width).lower(
            params, pool, pool, shaped((32, 1), jnp.int32),
            shaped((32,), jnp.int32), shaped((32, width), jnp.int32),
            shaped((32,), jnp.bool_)).compile()
    text = compiled.as_text()
    memory = compiled.memory_analysis()
    pool_bytes = 2 * int(np.prod(programs.pool_shape))
    assert memory.alias_size_in_bytes == 2 * pool_bytes
    assert memory.temp_size_in_bytes < 0.01e9
    assert _kernel_names(text) == ["paged_decode_attention"]
    # a pool (whole, flat or as the kernel views it), a layer's slab, a
    # gathered view of 32 slots x 256 or 128 blocks
    big = r"bf16\[(?:16,4096,16,8,128|65536,16,8,128|65536,128,128" \
          r"|4096,16,8,128|8192,16,8,128|32,4096,8,128)\]"
    opcodes = re.findall(
        r"= " + big + r"[^ ]* ([\w-]+)\(", text)
    assert opcodes and set(opcodes) <= {
        "parameter", "bitcast", "get-tuple-element", "scatter", "fusion"}
    # ... the fusions being the two in-place scatters of the tick's new
    # rows (a fusion each, the scatter its body)
    assert opcodes.count("fusion") == opcodes.count("scatter") == 2


# the routed layer's way back to the tokens (parallel/moe._sum_by_owner)
# at the benchmark's two routed shapes: (tokens, experts a token, width,
# buffer rows)
_WAY_BACK = {"smallthinker": (16384, 6, 2560, 30720),    # 157 MB, top-6
             "trinity": (16384, 8, 2048, 20480)}         # 84 MB, top-8


def _way_back_text(chip, fn, shape, out_dtype=jnp.bfloat16):
    """The chip's compiler's program for ``fn`` behind a producer inside
    the program (the table is ``out * w``, as the combine makes it)."""
    T, K, M, R = shape
    one_chip = SingleDeviceSharding(chip)

    def way_back(out, w, slot, valid):
        return fn((out * w).astype(jnp.bfloat16), slot, valid).astype(
            out_dtype)

    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in (
        ((R, M), jnp.bfloat16), ((R, 1), jnp.float32),
        ((T, K), jnp.int32), ((T, K), jnp.bool_))]
    with jax.enable_x64(False):
        return jax.jit(way_back).lower(*args).compile().as_text()


@pytest.mark.parametrize("out_dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("shape", sorted(_WAY_BACK))
def test_way_back_gathers_from_the_on_chip_memory(chip, shape, out_dtype):
    """Every gather over the slots reads a table the compiler keeps in
    the on-chip memory (``S(1)``) and takes the strategy that goes with
    it (6 ns a row on the chip against 46 from HBM), and the gathered
    rows are summed as they lie: no ``reshape bf16[16384,6,2560]`` copy
    (PERF.md, PR 35).  Both directions' result types."""
    from horovod_tpu.parallel import moe
    from tools import described_step

    text = _way_back_text(chip, moe._sum_by_owner, _WAY_BACK[shape],
                          out_dtype)
    found = described_step.gathers(text)
    assert len(found) == (2 if shape == "smallthinker" else 1)
    for _, _, table, strategy, _ in found:
        assert "S(1)" in table and strategy == "0", found
    assert not re.search(r" = bf16\[\d+,\d+,\d+\]\S* reshape\(", text)


def test_way_back_at_trinitys_shape_compiles_as_it_did(chip):
    """Top-8 and a table under the budget: the program is the one the
    function compiled to before it learned SmallThinker's shape."""
    from horovod_tpu.parallel import moe
    from tools.sum_by_owner_probe import stood

    def bare(text):     # the instructions, without the source lines
        return [re.sub(r", metadata=\{[^}]*\}", "", line)
                for line in text.splitlines() if " = " in line]

    now = bare(_way_back_text(chip, moe._sum_by_owner, _WAY_BACK["trinity"]))
    assert len(now) > 20
    assert now == bare(_way_back_text(chip, stood, _WAY_BACK["trinity"]))


def test_flash_backward_that_cannot_fit_vmem_names_the_bytes():
    """The backward kernel asks for the VMEM its shapes need; a
    sequence whose k, v, dk, dv and accumulators pass what a chip has
    is refused while tracing, with the bytes, not by the compiler."""
    from horovod_tpu.ops import pallas_kernels as pk

    asked = pk._flash_bwd_vmem_bytes(65536, 128, 512, 512, jnp.bfloat16)
    assert asked > pk._VMEM_USABLE_BYTES
    x = jax.ShapeDtypeStruct((1, 65536, 1, 128), jnp.bfloat16)
    with pytest.raises(ValueError, match=f"{asked} bytes.*S=65536"):
        jax.eval_shape(_grad_of_sum(_flash(), 3), x, x, x)
    # the cells' own shape asks a third of it
    assert pk._flash_bwd_vmem_bytes(
        8192, 128, 512, 512, jnp.bfloat16) < 40 << 20


def test_dp_step_allreduces_run_beside_compute(chips):
    """The data-parallel step program across four chips, as the TPU
    compiler schedules it: it takes the step's compiler options, each
    layer's gradient all-reduce sits in the backward loop's body, and
    all-reduces there and after the loop are asynchronous collective
    fusions, with compute beside them (by default every one is a
    synchronous ``all-reduce``)."""
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.models import (TransformerConfig, TransformerLM,
                                    make_fused_lm_loss)
    from horovod_tpu.ops.compiled import make_compiled_train_step
    from horovod_tpu.ops.xla_ops import MeshExecutor

    cfg = TransformerConfig(vocab_size=4096, d_model=1024, n_heads=8,
                            n_layers=2, d_ff=4096, max_seq_len=512,
                            dtype=jnp.bfloat16, remat=True,
                            remat_policy="dots")
    model = TransformerLM(cfg)
    optimizer = optax.adamw(1e-3)
    ex = MeshExecutor(chips, 4)
    assert ex.shard_mode

    def shaped(tree, spec):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(ex.mesh, spec)), tree)

    params = jax.eval_shape(
        lambda t: model.init(jax.random.PRNGKey(0), t)["params"],
        jax.ShapeDtypeStruct((1, 512), jnp.int32))
    state = shaped(jax.eval_shape(
        lambda p: {"params": p, "opt_state": optimizer.init(p)}, params),
        P())
    batch = shaped(jax.ShapeDtypeStruct((4, 4, 512), jnp.int32), P("hvd"))
    step = make_compiled_train_step(
        make_fused_lm_loss(model, n_chunks=4), optimizer)
    assert step._compiler_options(ex)
    with jax.enable_x64(False):
        text = step._build(ex).lower(state, batch).compile().as_text()
    # {computation: its instructions}
    bodies, name = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            name = head.group(1)
            bodies[name] = []
        elif name and line.strip() != "}":
            bodies[name].append(line)
    fused = {n: "\n".join(b) for n, b in bodies.items()
             if n.startswith("async_collective_fusion")
             and any(" all-reduce(" in line for line in b)}
    in_loop = [b for b in fused.values()
               if "while/body" in b and "hvd_step/grad_reduce" in b]
    # a layer's (1024, 4096) MLP gradient, reduced beside a matmul
    assert any(re.search(r"f32\[1024,4096\]\S* all-reduce\(", b)
               and " convolution(" in b for b in in_loop), len(fused)
    # the embedding's, after the loop, beside the optimizer's update
    assert any(re.search(r"f32\[4096,1024\]\S* all-reduce\(", b)
               and "hvd_step/optimizer" in b for b in fused.values())
    # what stays synchronous is small, and no stacked leaf is among it
    alone = [hit.group(1) for n, b in bodies.items()
             if "fused_computation" not in n and n not in fused
             for line in b
             for hit in [re.search(r" = (.*?) all-reduce\(", line)] if hit]
    assert alone and not any(
        dims.startswith("2,") or np.prod([int(d) for d in dims.split(",")
                                          if d]) * 4 >= 1 << 20
        for result in alone
        for dims in re.findall(r"f32\[([\d,]*)\]", result)), alone


def test_int4_codec_matmul_pack_is_exact():
    """The repaired int4 kernels pack and unpack nibbles with MXU
    products of small integers: every code pair, at both parities,
    round-trips bit-exactly against the numpy codec."""
    from horovod_tpu.ops import quantize as qz
    from horovod_tpu.ops.pallas_kernels import (
        dequantize_blockwise_int4, quantize_blockwise_int4)

    lo, hi = np.meshgrid(np.arange(-7, 8), np.arange(-7, 8))
    codes = np.stack([lo.ravel(), hi.ravel()], axis=1).reshape(-1)
    x = np.zeros(512, np.float32)
    x[:codes.size] = codes          # absmax 7 per block: scale 1.0
    x[256] = 7.0
    qn, sn, n = qz.np_quantize_blockwise_int4(x)
    q, s = quantize_blockwise_int4(jnp.asarray(x), interpret=True)
    np.testing.assert_array_equal(np.asarray(q)[:qn.size], qn)
    out = dequantize_blockwise_int4(q, s, n, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), x)


def test_build_mesh_on_described_chips(chip, monkeypatch, caplog):
    """On TPU devices build_mesh maps the axes onto the torus; where
    no assignment exists (a size-2 axis on a 4x4 slice) it says so and
    falls back to device order — and nothing else is swallowed."""
    from jax.experimental import topologies

    from horovod_tpu.parallel import mesh as mesh_mod

    def described(name):
        devices = topologies.get_topology_desc(
            platform="tpu", topology_name=name).devices
        monkeypatch.setattr(mesh_mod.jax, "devices", lambda: devices)
        return devices

    described("v5e:2x2")
    mesh = mesh_mod.build_mesh(dp=2, tp=2)
    assert [d.id for d in mesh.devices.flat] == [0, 1, 3, 2]
    assert not caplog.records

    devices = described("v5e:4x4")
    with caplog.at_level("WARNING", logger="horovod_tpu"):
        mesh = mesh_mod.build_mesh(dp=8, tp=2)
    assert "row-major" in caplog.text
    assert list(mesh.devices.flat) == list(devices)


# ---------------------------------------------------------------------------
# chip_smoke.py control flow, compile cache, one process per chip

def _json_lines(text):
    return [json.loads(line) for line in text.splitlines()
            if line.startswith("{")]


def test_smoke_rehearsal_passes_every_phase(hvd_shutdown, capsys):
    """A tiny config through every one-chip phase, the kernels in the
    interpret mode the rehearsal asks for."""
    import chip_smoke

    assert chip_smoke.run(chips=1, rehearse=True) == 0
    lines = _json_lines(capsys.readouterr().out)
    assert [ln.get("phase") for ln in lines[:-1]] == [
        "environment", "native", "eager", "train"]
    train = lines[-2]
    assert train["program_cache_misses_after_warmup"] == 0
    assert train["losses"][-1] < train["losses"][0]
    assert train["tpu_custom_calls"] == 0       # interpreted, as asked
    assert lines[-1] == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": len(jax.devices())}}


def test_smoke_fails_off_the_chip():
    """``python chip_smoke.py`` where the platform is not tpu: a
    non-zero exit and ``"ok": false`` on the last line."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=120)
    assert proc.returncode != 0
    last = json.loads(proc.stdout.splitlines()[-1])
    assert last["ok"] is False
    assert last["device"]["platform"] == "cpu"
    assert "not 'tpu'" in proc.stdout


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_placement(monkeypatch, from_env):
    from jax.experimental.compilation_cache import compilation_cache

    from horovod_tpu.utils.compile_cache import place_compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        if from_env:
            # jax read the variable itself at import: nothing is set
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
            place_compile_cache()
            assert jax.config.jax_compilation_cache_dir == before
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR",
                               raising=False)
            fixed = os.path.join(REPO, ".jax_cache")
            assert place_compile_cache() == fixed
            assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()


def test_unknown_device_kind_has_no_peak():
    from chipbench import flops

    assert flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(ValueError, match="no published peaks"):
        flops.peaks(jax.devices()[0].device_kind)


def test_importing_the_launcher_starts_no_backend():
    """A launcher parent that touched a backend would hold the chip
    its one worker needs."""
    code = ("import horovod_tpu, horovod_tpu.runner.launch, "
            "horovod_tpu.runner.proc_run\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized()\n")
    subprocess.run([sys.executable, "-c", code], cwd=REPO, check=True,
                   timeout=120)


def test_launcher_refuses_two_chip_processes_on_one_host():
    """Without --cpu every local worker process would open every chip
    of the host; the launcher says so before it spawns anything."""
    from horovod_tpu.runner.proc_run import launch_procs

    with pytest.raises(ValueError, match="one process"):
        launch_procs([sys.executable, "-c", "pass"], np=2, platform=None)
