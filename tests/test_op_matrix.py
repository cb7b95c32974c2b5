"""Generated op-matrix sweep — the reference's parallel test grid
(``test/parallel/test_tensorflow.py`` 5601 LoC / ``test_torch.py``
4416 LoC sweep op x dtype x fused/unfused x prescale/postscale x
process-set x grouped x joined).  Here the grid is GENERATED
(pytest.mark.parametrize over the cross-products) instead of
hand-listed, and all cells share one live engine (module-scoped init)
so the whole matrix runs in seconds.

Each cell asserts exact numerics on every rank.
"""

import numpy as np
import pytest

import horovod_tpu as hvd

try:
    import ml_dtypes

    BF16 = ml_dtypes.bfloat16
except ImportError:  # pragma: no cover
    BF16 = None

NP = 4

INT_DTYPES = ["int8", "uint8", "int32", "int64"]
FLOAT_DTYPES = ["float16", "float32", "float64"] + \
    (["bfloat16"] if BF16 is not None else [])
ALL_DTYPES = INT_DTYPES + FLOAT_DTYPES

# tolerance per dtype: low-precision dtypes accumulate rounding
TOL = {"float16": 1e-2, "bfloat16": 1e-1}


def _dt(name):
    return BF16 if name == "bfloat16" else np.dtype(name)


def _tol(name):
    return TOL.get(name, 1e-6)


def _is_float(name):
    return name in FLOAT_DTYPES


@pytest.fixture(scope="module")
def live_engine():
    """One engine for the whole matrix (the reference's parallel tests
    similarly init once per process)."""
    if hvd.is_initialized():
        hvd.shutdown()
    hvd.run(lambda: None, np=NP, keep_alive=True)
    yield
    hvd.shutdown()


def run_ranks(fn):
    return hvd.run(fn, np=NP)


def _make(dtype_name, n=8, scale=1, offset=0):
    base = np.arange(1, n + 1)
    arr = (base * scale + offset)
    if _is_float(dtype_name):
        return arr.astype(_dt(dtype_name))
    return np.mod(arr, 63).astype(_dt(dtype_name))


# ---------------------------------------------------------------------------
# allreduce: op x dtype

REDUCE_CASES = [("sum", d) for d in ALL_DTYPES] + \
    [("min", d) for d in ALL_DTYPES] + \
    [("max", d) for d in ALL_DTYPES] + \
    [("product", d) for d in ("int32", "int64", "float32", "float64")] + \
    [("average", d) for d in FLOAT_DTYPES] + \
    [("adasum", d) for d in ("float32", "float64")]

_OPS = {"sum": hvd.Sum, "min": hvd.Min, "max": hvd.Max,
        "product": hvd.Product, "average": hvd.Average,
        "adasum": hvd.Adasum}


def _expected_reduce(op_name, rows):
    stack = np.stack([r.astype(np.float64) for r in rows])
    if op_name == "sum":
        return stack.sum(0)
    if op_name == "min":
        return stack.min(0)
    if op_name == "max":
        return stack.max(0)
    if op_name == "product":
        return stack.prod(0)
    if op_name == "average":
        return stack.mean(0)
    raise AssertionError(op_name)


@pytest.mark.parametrize("op_name,dtype", REDUCE_CASES,
                         ids=[f"{o}-{d}" for o, d in REDUCE_CASES])
def test_allreduce_matrix(live_engine, op_name, dtype):
    def fn():
        r = hvd.rank()
        x = _make(dtype, scale=r + 1)
        out = hvd.allreduce(x, op=_OPS[op_name],
                            name=f"m.ar.{op_name}.{dtype}")
        assert str(out.dtype) == dtype or out.dtype == _dt(dtype)
        return np.asarray(out, np.float64), np.asarray(x, np.float64)

    results = run_ranks(fn)
    rows = [x for _, x in results]
    if op_name == "adasum":
        # adasum: scalar-projection pairwise combine; exact value is
        # implementation-defined — assert rank agreement + finiteness
        outs = [o for o, _ in results]
        for o in outs[1:]:
            assert np.allclose(o, outs[0])
        assert np.all(np.isfinite(outs[0]))
        return
    expected = _expected_reduce(op_name, rows)
    if not _is_float(dtype):
        # small ints wrap modularly: compute in int64, cast to dtype
        expected = _expected_reduce(
            op_name, [x.astype(np.int64) for x in rows]).astype(
                _dt(dtype)).astype(np.float64)
    for out, _ in results:
        assert np.allclose(out, expected, atol=_tol(dtype)), \
            (op_name, dtype, out, expected)


def test_allreduce_int_average_reference_semantics(live_engine):
    """Int average follows the reference (test_torch.py:201-230): sum,
    divide in FP64, truncating cast — equal inputs come back exact."""
    def fn():
        out = hvd.allreduce(np.arange(-4, 4, dtype=np.int32),
                            op=hvd.Average, name="m.avg.int32")
        assert out.dtype == np.int32
        return out

    for out in run_ranks(fn):
        np.testing.assert_array_equal(
            out, np.arange(-4, 4, dtype=np.int32))


# ---------------------------------------------------------------------------
# prescale / postscale x float dtype

SCALE_CASES = [(d, pre, post) for d in FLOAT_DTYPES
               for pre, post in ((2.0, 1.0), (1.0, 0.5), (0.5, 2.0))]


@pytest.mark.parametrize("dtype,pre,post", SCALE_CASES,
                         ids=[f"{d}-pre{p}-post{q}"
                              for d, p, q in SCALE_CASES])
def test_allreduce_scale_matrix(live_engine, dtype, pre, post):
    def fn():
        r = hvd.rank()
        x = np.ones(6).astype(_dt(dtype)) * (r + 1)
        out = hvd.allreduce(x, op=hvd.Sum, prescale_factor=pre,
                            postscale_factor=post,
                            name=f"m.sc.{dtype}.{pre}.{post}")
        return np.asarray(out, np.float64)

    expected = pre * post * sum(range(1, NP + 1))
    for out in run_ranks(fn):
        assert np.allclose(out, expected, atol=_tol(dtype) * 10), \
            (out, expected)


@pytest.mark.parametrize("dtype", INT_DTYPES)
def test_allreduce_int_scale_reference_semantics(live_engine, dtype):
    """Int prescale follows the reference (test_torch.py:434-487):
    factor applied in FP64, truncating cast back, then summed."""
    def fn():
        x = _make(dtype)
        out = hvd.allreduce(x, op=hvd.Sum, prescale_factor=2.5,
                            name=f"m.isc.{dtype}")
        assert out.dtype == _dt(dtype)
        return (np.asarray(out, np.float64),
                np.asarray(x, np.float64))

    results = run_ranks(fn)
    per_rank = [np.trunc(x * 2.5).astype(_dt(dtype)).astype(np.float64)
                for _, x in results]
    expected = np.sum(per_rank, axis=0)
    # modular wrap for small ints, matching on-wire arithmetic
    expected = expected.astype(_dt(dtype)).astype(np.float64)
    for out, _ in results:
        assert np.allclose(out, expected), (dtype, out, expected)


# ---------------------------------------------------------------------------
# grouped allreduce: op x dtype (homogeneous) + mixed-dtype groups

GROUPED_CASES = [("sum", d) for d in ALL_DTYPES] + \
    [("average", d) for d in FLOAT_DTYPES]


@pytest.mark.parametrize("op_name,dtype", GROUPED_CASES,
                         ids=[f"{o}-{d}" for o, d in GROUPED_CASES])
def test_grouped_allreduce_matrix(live_engine, op_name, dtype):
    def fn():
        r = hvd.rank()
        xs = [_make(dtype, n=5, scale=r + 1),
              _make(dtype, n=3, scale=r + 1, offset=1)]
        outs = hvd.grouped_allreduce(
            xs, op=_OPS[op_name], name=f"m.gar.{op_name}.{dtype}")
        return ([np.asarray(o, np.float64) for o in outs],
                [np.asarray(x, np.float64) for x in xs])

    results = run_ranks(fn)
    for k in range(2):
        rows = [xs[k] for _, xs in results]
        expected = _expected_reduce(op_name, rows)
        for outs, _ in results:
            assert np.allclose(outs[k], expected,
                               atol=_tol(dtype)), (op_name, dtype)


def test_grouped_mixed_dtype_group(live_engine):
    def fn():
        r = hvd.rank()
        xs = [np.ones(4, np.float32) * (r + 1),
              np.arange(6, dtype=np.int32),
              np.ones(2, np.float64) * r]
        outs = hvd.grouped_allreduce(xs, op=hvd.Sum, name="m.gmix")
        assert np.allclose(outs[0], sum(range(1, NP + 1)))
        assert np.array_equal(outs[1], np.arange(6) * NP)
        assert np.allclose(outs[2], sum(range(NP)))
        return True

    assert all(run_ranks(fn))


# ---------------------------------------------------------------------------
# allgather: dtype x (even | uneven first dim)

GATHER_CASES = [(d, kind) for d in ALL_DTYPES
                for kind in ("even", "uneven")]


@pytest.mark.parametrize("dtype,kind", GATHER_CASES,
                         ids=[f"{d}-{k}" for d, k in GATHER_CASES])
def test_allgather_matrix(live_engine, dtype, kind):
    def fn():
        r = hvd.rank()
        rows = r + 1 if kind == "uneven" else 2
        x = np.full((rows, 3), r + 1).astype(_dt(dtype))
        out = hvd.allgather(x, name=f"m.ag.{dtype}.{kind}")
        return np.asarray(out, np.float64)

    if kind == "uneven":
        expected = np.concatenate(
            [np.full((i + 1, 3), i + 1) for i in range(NP)])
    else:
        expected = np.concatenate(
            [np.full((2, 3), i + 1) for i in range(NP)])
    for out in run_ranks(fn):
        assert np.array_equal(out, expected), (dtype, kind)


# ---------------------------------------------------------------------------
# broadcast: dtype x root

BCAST_CASES = [(d, root) for d in ALL_DTYPES for root in (0, NP - 1)]


@pytest.mark.parametrize("dtype,root", BCAST_CASES,
                         ids=[f"{d}-root{r}" for d, r in BCAST_CASES])
def test_broadcast_matrix(live_engine, dtype, root):
    def fn():
        r = hvd.rank()
        x = _make(dtype, scale=r + 1)
        out = hvd.broadcast(x, root_rank=root,
                            name=f"m.bc.{dtype}.{root}")
        return np.asarray(out, np.float64)

    expected = np.asarray(_make(dtype, scale=root + 1), np.float64)
    for out in run_ranks(fn):
        assert np.array_equal(out, expected), (dtype, root)


# ---------------------------------------------------------------------------
# alltoall: dtype x (equal | ragged splits)

A2A_CASES = [(d, kind) for d in ("int32", "int64", "float32",
                                 "float64", "bfloat16")
             if d in ALL_DTYPES for kind in ("equal", "ragged")]


@pytest.mark.parametrize("dtype,kind", A2A_CASES,
                         ids=[f"{d}-{k}" for d, k in A2A_CASES])
def test_alltoall_matrix(live_engine, dtype, kind):
    def fn():
        r = hvd.rank()
        if kind == "equal":
            splits = np.ones(NP, np.int32)
            x = (np.arange(NP) + 10 * r).astype(_dt(dtype))
        else:
            # rank r sends p+1 elements to peer p, all valued r
            splits = np.arange(1, NP + 1, dtype=np.int32)
            x = np.full(int(splits.sum()), r).astype(_dt(dtype))
        out, recv = hvd.alltoall(x, splits=splits,
                                 name=f"m.a2a.{dtype}.{kind}")
        return np.asarray(out, np.float64), np.asarray(recv)

    results = run_ranks(fn)
    for r, (out, recv) in enumerate(results):
        if kind == "equal":
            expected = np.array([r + 10 * p for p in range(NP)],
                                np.float64)
            assert np.array_equal(out, expected), (dtype, r)
        else:
            # rank r receives r+1 elements from each peer p, valued p
            expected = np.concatenate(
                [np.full(r + 1, p) for p in range(NP)]).astype(
                    np.float64)
            assert np.array_equal(out, expected), (dtype, r)
            assert np.array_equal(recv, np.full(NP, r + 1))


# ---------------------------------------------------------------------------
# reducescatter: op x dtype (+ uneven dim0)

RS_CASES = [("sum", d) for d in ("int32", "int64", "float32",
                                 "float64", "float16")
            if d in ALL_DTYPES] + \
    [("average", d) for d in ("float32", "float64")]


@pytest.mark.parametrize("op_name,dtype", RS_CASES,
                         ids=[f"{o}-{d}" for o, d in RS_CASES])
def test_reducescatter_matrix(live_engine, op_name, dtype):
    def fn():
        r = hvd.rank()
        x = (np.arange(NP * 2 * 3).reshape(NP * 2, 3) * (r + 1)) \
            .astype(_dt(dtype))
        out = hvd.reducescatter(x, op=_OPS[op_name],
                                name=f"m.rs.{op_name}.{dtype}")
        return np.asarray(out, np.float64), r

    scale = sum(range(1, NP + 1)) if op_name == "sum" \
        else np.mean(range(1, NP + 1))
    base = np.arange(NP * 2 * 3, dtype=np.float64).reshape(NP * 2, 3)
    for out, r in run_ranks(fn):
        expected = base[r * 2:(r + 1) * 2] * scale
        assert np.allclose(out, expected, atol=_tol(dtype) * 100), \
            (op_name, dtype, r)


@pytest.mark.parametrize("dtype", ["float32", "int64"])
def test_reducescatter_uneven_matrix(live_engine, dtype):
    """dim0 not divisible by NP: late ranks get smaller chunks."""
    def fn():
        r = hvd.rank()
        x = np.ones((NP * 2 + 1, 2)).astype(_dt(dtype)) * (r + 1)
        out = hvd.reducescatter(x, op=hvd.Sum,
                                name=f"m.rsu.{dtype}")
        return out.shape[0], np.asarray(out, np.float64), r

    total = sum(range(1, NP + 1))
    sizes = [3, 2, 2, 2]        # ceil-first chunking of 9 rows
    for n0, out, r in run_ranks(fn):
        assert n0 == sizes[r], (n0, r)
        assert np.allclose(out, total)


# ---------------------------------------------------------------------------
# process-set scoped: op x dtype

PS_CASES = [(op, d) for op in ("allreduce", "allgather", "broadcast",
                               "reducescatter")
            for d in ("float32", "float64", "int32", "bfloat16")
            if d in ALL_DTYPES]


@pytest.mark.parametrize("op_name,dtype", PS_CASES,
                         ids=[f"{o}-{d}" for o, d in PS_CASES])
def test_process_set_matrix(live_engine, op_name, dtype):
    def fn():
        ps = hvd.add_process_set([1, 2])
        try:
            r = hvd.rank()
            if r in (1, 2):
                x = np.ones(4).astype(_dt(dtype)) * (r + 1)
                if op_name == "allreduce":
                    out = hvd.allreduce(
                        x, op=hvd.Sum, process_set=ps,
                        name=f"m.ps.ar.{dtype}")
                    assert np.allclose(np.asarray(out, np.float64), 5.0)
                elif op_name == "allgather":
                    out = hvd.allgather(
                        x.reshape(1, -1), process_set=ps,
                        name=f"m.ps.ag.{dtype}")
                    assert out.shape == (2, 4)
                elif op_name == "broadcast":
                    out = hvd.broadcast(
                        x, root_rank=2, process_set=ps,
                        name=f"m.ps.bc.{dtype}")
                    assert np.allclose(np.asarray(out, np.float64), 3.0)
                else:
                    xx = np.ones((2, 2)).astype(_dt(dtype)) * (r + 1)
                    out = hvd.reducescatter(
                        xx, op=hvd.Sum, process_set=ps,
                        name=f"m.ps.rs.{dtype}")
                    assert np.allclose(np.asarray(out, np.float64), 5.0)
            return True
        finally:
            # removal is a BARRIER across local rank threads: every
            # rank votes (engine.remove_process_set contract)
            hvd.remove_process_set(ps)

    assert all(run_ranks(fn))


# ---------------------------------------------------------------------------
# grouped x process-set x prescale (the cross-product VERDICT named)

@pytest.mark.parametrize("dtype", FLOAT_DTYPES)
def test_grouped_ps_prescale_matrix(live_engine, dtype):
    def fn():
        ps = hvd.add_process_set([0, 3])
        try:
            r = hvd.rank()
            if r in (0, 3):
                xs = [(np.ones(4) * (r + 1)).astype(_dt(dtype)),
                      np.ones(2).astype(_dt(dtype))]
                outs = hvd.grouped_allreduce(
                    xs, op=hvd.Sum, prescale_factor=2.0,
                    process_set=ps, name=f"m.gps.{dtype}")
                assert np.allclose(np.asarray(outs[0], np.float64),
                                   2.0 * 5.0, atol=_tol(dtype) * 10)
                assert np.allclose(np.asarray(outs[1], np.float64),
                                   4.0, atol=_tol(dtype) * 10)
            return True
        finally:
            hvd.remove_process_set(ps)

    assert all(run_ranks(fn))


# ---------------------------------------------------------------------------
# join (late/absent rank) x dtype

@pytest.mark.parametrize("dtype", ["float32", "float64", "int32"])
def test_join_matrix(live_engine, dtype):
    """Rank 3 joins instead of reducing: the collective completes over
    the contributors with zero contribution from the joined rank."""
    def fn():
        r = hvd.rank()
        if r == 3:
            hvd.join()
            return None
        x = np.ones(4).astype(_dt(dtype)) * (r + 1)
        out = hvd.allreduce(x, op=hvd.Sum, name=f"m.join.{dtype}")
        hvd.join()
        return np.asarray(out, np.float64)

    results = run_ranks(fn)
    for r, out in enumerate(results):
        if r == 3:
            assert out is None
        else:
            assert np.allclose(out, 1 + 2 + 3), (r, out)


# ---------------------------------------------------------------------------
# compiled (in-program) allreduce matrix

COMPILED_CASES = [("sum", d) for d in ALL_DTYPES] + \
    [("average", d) for d in FLOAT_DTYPES]


@pytest.mark.parametrize("op_name,dtype", COMPILED_CASES,
                         ids=[f"{o}-{d}" for o, d in COMPILED_CASES])
def test_compiled_allreduce_matrix(live_engine, op_name, dtype):
    def fn():
        r = hvd.rank()
        x = _make(dtype, scale=r + 1)
        out = hvd.compiled_allreduce(x, op=_OPS[op_name])
        return np.asarray(out, np.float64), np.asarray(x, np.float64)

    results = run_ranks(fn)
    rows = [x for _, x in results]
    expected = _expected_reduce(op_name, rows)
    for out, _ in results:
        assert np.allclose(out, expected, atol=_tol(dtype)), \
            (op_name, dtype)


# ---------------------------------------------------------------------------
# in-place variants: dtype sweep (numpy targets are mutable)

@pytest.mark.parametrize("dtype", ALL_DTYPES)
def test_allreduce_inplace_matrix(live_engine, dtype):
    def fn():
        r = hvd.rank()
        x = _make(dtype, scale=r + 1)
        ref = [_make(dtype, scale=i + 1) for i in range(NP)]
        out = hvd.allreduce_(x, op=hvd.Sum, name=f"m.ip.{dtype}")
        assert out is x        # wrote back into the caller's buffer
        expected = _expected_reduce(
            "sum", [v.astype(np.int64) if not _is_float(dtype)
                    else v for v in ref]).astype(_dt(dtype))
        assert np.allclose(np.asarray(x, np.float64),
                           np.asarray(expected, np.float64),
                           atol=_tol(dtype))
        return True

    assert all(run_ranks(fn))


# ---------------------------------------------------------------------------
# async handles: submit-many then synchronize, per dtype

@pytest.mark.parametrize("dtype", ["float32", "float64", "int32",
                                   "bfloat16"])
def test_async_handles_matrix(live_engine, dtype):
    def fn():
        r = hvd.rank()
        if dtype == "bfloat16" and BF16 is None:
            return True
        handles = [
            hvd.allreduce_async(
                (np.ones(4) * (r + 1) * (k + 1)).astype(_dt(dtype)),
                op=hvd.Sum, name=f"m.async.{dtype}.{k}")
            for k in range(4)
        ]
        for k, h in enumerate(handles):
            out = hvd.synchronize(h)
            expected = (k + 1) * sum(range(1, NP + 1))
            assert np.allclose(np.asarray(out, np.float64), expected)
        return True

    assert all(run_ranks(fn))


# ---------------------------------------------------------------------------
# grouped allgather / reducescatter dtype cells

@pytest.mark.parametrize("dtype", ["float32", "int64"])
def test_grouped_allgather_matrix(live_engine, dtype):
    def fn():
        r = hvd.rank()
        xs = [np.full((r + 1, 2), r).astype(_dt(dtype)),
              np.full((1, 3), r + 10).astype(_dt(dtype))]
        outs = hvd.grouped_allgather(xs, name=f"m.gag.{dtype}")
        assert outs[0].shape == (sum(range(1, NP + 1)), 2)
        assert outs[1].shape == (NP, 3)
        assert np.allclose(np.asarray(outs[1], np.float64)[:, 0],
                           np.arange(10, 10 + NP))
        return True

    assert all(run_ranks(fn))


@pytest.mark.parametrize("dtype", ["float32", "float64"])
def test_grouped_reducescatter_matrix(live_engine, dtype):
    def fn():
        r = hvd.rank()
        xs = [np.ones((NP * 2, 2)).astype(_dt(dtype)) * (r + 1),
              np.ones((NP, 3)).astype(_dt(dtype)) * (r + 1)]
        outs = hvd.grouped_reducescatter(
            xs, op=hvd.Sum, name=f"m.grs.{dtype}")
        total = sum(range(1, NP + 1))
        assert outs[0].shape == (2, 2)
        assert outs[1].shape == (1, 3)
        assert np.allclose(np.asarray(outs[0], np.float64), total)
        assert np.allclose(np.asarray(outs[1], np.float64), total)
        return True

    assert all(run_ranks(fn))


# ---------------------------------------------------------------------------
# wire compression: (none | fp16 | int8) x (allreduce | grouped |
# reducescatter) x (engine | compiled).  int8 is the block-scaled
# quantized wire (ops/quantize.py); its tolerance follows the codec's
# error bound (absmax/254 per element per rank).

# int4's bound follows its codec: error <= absmax/14 per element per
# rank (test_pallas int4 error bound), absmax ~3.5 for the N(0,1)
# payloads below, summed over NP ranks
WIRE_ATOL = {None: 1e-5, "fp16": 3e-2, "int8": 2e-1, "int4": 1.6}

WIRE_CASES = [
    (w, o, p)
    for w in (None, "fp16", "int8", "int4")
    for o in ("allreduce", "grouped_allreduce", "reducescatter")
    for p in ("engine", "compiled")
]


@pytest.mark.parametrize(
    "wire,op_kind,path", WIRE_CASES,
    ids=[f"{w or 'f32'}-{o}-{p}" for w, o, p in WIRE_CASES])
def test_wire_compression_matrix(live_engine, wire, op_kind, path):
    if path == "compiled" and op_kind == "reducescatter":
        pytest.skip("compiled surface is allreduce-only "
                    "(ops/compiled.py)")
    tag = f"{wire or 'f32'}.{op_kind}.{path}"

    def fn():
        r = hvd.rank()
        rng = np.random.default_rng(r)
        if op_kind == "reducescatter":
            x = rng.standard_normal((NP * 2, 5)).astype(np.float32)
            out = hvd.reducescatter(x, op=hvd.Sum,
                                    name=f"m.wire.{tag}",
                                    wire_dtype=wire)
            return np.asarray(out, np.float64), x, r
        x = rng.standard_normal(1000).astype(np.float32)
        if op_kind == "allreduce":
            if path == "compiled":
                out = hvd.compiled_allreduce(x, op=hvd.Sum,
                                             wire_dtype=wire)
            else:
                out = hvd.allreduce(x, op=hvd.Sum,
                                    name=f"m.wire.{tag}",
                                    wire_dtype=wire)
            return np.asarray(out, np.float64), x, r
        xs = [x[:600], x[600:]]
        if path == "compiled":
            outs = hvd.compiled_grouped_allreduce(xs, op=hvd.Sum,
                                                  wire_dtype=wire)
        else:
            outs = hvd.grouped_allreduce(xs, op=hvd.Sum,
                                         name=f"m.wire.{tag}",
                                         wire_dtype=wire)
        return np.concatenate([np.asarray(o, np.float64)
                               for o in outs]), x, r

    results = run_ranks(fn)
    expected = np.sum([x.astype(np.float64) for _, x, _ in results],
                      axis=0)
    for out, _, r in results:
        want = expected[r * 2:(r + 1) * 2] \
            if op_kind == "reducescatter" else expected
        assert np.allclose(out, want, atol=WIRE_ATOL[wire]), \
            (wire, op_kind, path, np.abs(out - want).max())


def test_int8_wire_accounting(live_engine):
    """The engine's wire accounting must show the ~3.97x reduction the
    int8 format promises (1 byte/elem + 2 bytes/256-elem block vs 4)."""
    from horovod_tpu.common import basics
    eng = basics.engine()
    l0, a0 = eng.logical_wire_bytes, eng.actual_wire_bytes
    q0 = eng.quantized_bucket_runs

    def fn():
        x = np.ones(1 << 16, np.float32)
        hvd.allreduce(x, op=hvd.Sum, name="m.acct", wire_dtype="int8")
        return True

    assert all(run_ranks(fn))
    dl = eng.logical_wire_bytes - l0
    da = eng.actual_wire_bytes - a0
    assert eng.quantized_bucket_runs > q0
    assert dl > 0 and dl / da > 3.9, (dl, da)


def test_compiled_int8_stays_single_program(live_engine):
    """Quantized compiled-path allreduce must remain ONE cached XLA
    program across steps — encode, psum of integer partials, and
    decode all live inside it (no per-step retrace).  Its transport is
    the psum operand: int16 partial sums at this world size, so the
    honest accounting shows ~2x under f32 (the ~4x codec wire belongs
    to the engine's all_gather-of-codes path)."""
    def fn():
        red = hvd.CompiledGroupedAllreduce(
            op=hvd.Average, wire_dtype="int8", error_feedback=True,
            force_program=True)
        rng = np.random.default_rng(hvd.rank())
        xs = [rng.standard_normal(500).astype(np.float32),
              rng.standard_normal(300).astype(np.float32)]
        for _ in range(4):
            red(xs)
        ratio = red.last_logical_bytes / red.last_wire_bytes
        assert 1.9 < ratio <= 2.0, ratio
        return len(red._programs)

    assert all(n == 1 for n in run_ranks(fn))


def test_explicit_f32_wire_overrides_default(live_engine):
    """wire_dtype='f32' must force a full-width reduction even when a
    process-wide default (HOROVOD_WIRE_DTYPE / autotune) says int8 —
    users need a lossless escape hatch for metrics/validation."""
    from horovod_tpu.common import basics
    eng = basics.engine()
    old = eng.config.wire_dtype
    eng.config.wire_dtype = "int8"
    try:
        q0 = eng.quantized_bucket_runs

        def fn_f32():
            x = np.full(2048, float(hvd.rank() + 1), np.float32)
            return hvd.allreduce(x, op=hvd.Sum, name="m.wire.exp32",
                                 wire_dtype="f32")

        outs = run_ranks(fn_f32)
        assert eng.quantized_bucket_runs == q0, "f32 override ignored"
        expect = sum(range(1, NP + 1))
        for o in outs:
            np.testing.assert_array_equal(np.asarray(o),
                                          np.full(2048, expect))

        def fn_default():
            x = np.full(2048, float(hvd.rank() + 1), np.float32)
            return hvd.allreduce(x, op=hvd.Sum, name="m.wire.dflt")

        run_ranks(fn_default)
        assert eng.quantized_bucket_runs > q0, \
            "config default not honored"
    finally:
        eng.config.wire_dtype = old


def test_wire_dtype_skips_nonlinear_ops(live_engine):
    """Min/max/product do not commute with per-rank decode — the
    engine must silently ship them full width, not corrupt them."""
    def fn():
        r = hvd.rank()
        x = np.arange(1, 9, dtype=np.float32) * (r + 1)
        out = hvd.allreduce(x, op=hvd.Max, name="m.wire.max",
                            wire_dtype="int8")
        return np.asarray(out, np.float64)

    expected = np.arange(1, 9, dtype=np.float64) * NP
    for out in run_ranks(fn):
        np.testing.assert_array_equal(out, expected)


# ---------------------------------------------------------------------------
# topology-aware algorithms (ISSUE 2): algorithm x op x wire dtype x
# path matrix — every cell must match the flat f32 reduction within
# the wire format's tolerance — plus the topology cases: hierarchical
# cross-byte budget on a (simulated) two-host layout, heterogeneous
# host:slots fallback, and a dp x tp mesh torus via TopologyHint.


@pytest.fixture()
def two_host_topology(live_engine):
    """Patch a 2-hosts-x-2-slots layout onto the live engine (the
    launcher's HOROVOD_TPU_HOST_OF_RANK handoff, simulated
    in-process so the matrix runs on the module-scoped engine)."""
    from horovod_tpu.common import basics
    from horovod_tpu.common.topology import Topology

    eng = basics.engine()
    old = eng.topology
    eng.topology = Topology(size=NP, host_of_rank=[0, 0, 1, 1])
    yield eng
    eng.topology = old


ALGO_CASES = [
    (a, o, w, p)
    for a in ("hierarchical", "torus")
    for o in ("sum", "average")
    for w in (None, "fp16", "int8")
    for p in ("engine", "compiled")
]


@pytest.mark.parametrize(
    "algo,op_name,wire,path", ALGO_CASES,
    ids=[f"{a}-{o}-{w or 'f32'}-{p}" for a, o, w, p in ALGO_CASES])
def test_algorithm_matrix(two_host_topology, algo, op_name, wire, path):
    eng = two_host_topology
    runs0 = dict(eng.algo_runs)
    tag = f"{algo}.{op_name}.{wire or 'f32'}.{path}"

    def fn():
        r = hvd.rank()
        rng = np.random.default_rng(r)
        x = rng.standard_normal(1000).astype(np.float32)
        if path == "compiled":
            out = hvd.compiled_allreduce(
                x, op=_OPS[op_name], algorithm=algo, wire_dtype=wire)
        else:
            out = hvd.allreduce(x, op=_OPS[op_name],
                                name=f"m.algo.{tag}",
                                algorithm=algo, wire_dtype=wire)
        return np.asarray(out, np.float64), x

    results = run_ranks(fn)
    stack = np.stack([x.astype(np.float64) for _, x in results])
    expected = stack.sum(0) if op_name == "sum" else stack.mean(0)
    tol = WIRE_ATOL[wire]
    for out, _ in results:
        assert np.allclose(out, expected, atol=tol), \
            (algo, op_name, wire, path, np.abs(out - expected).max())
    if path == "engine":
        # the engine really took the decomposed path (not a silent
        # flat fallback)
        assert eng.algo_runs.get(algo, 0) > runs0.get(algo, 0), \
            (algo, runs0, eng.algo_runs)


def test_hierarchical_cross_byte_budget(two_host_topology):
    """ISSUE 2 acceptance: hierarchical moves <= (1/local_size + eps)
    of the logical bytes across the cross-host hop, asserted via the
    engine's wire-byte accounting; the int8 wire shrinks that hop a
    further ~2x (integer partials + shared scales)."""
    eng = two_host_topology

    def run_one(wire, name):
        l0, c0 = eng.logical_wire_bytes, eng.cross_wire_bytes

        def fn():
            x = np.ones(1 << 14, np.float32) * (hvd.rank() + 1)
            hvd.allreduce(x, op=hvd.Sum, name=name,
                          algorithm="hierarchical", wire_dtype=wire)
            return True

        assert all(run_ranks(fn))
        return (eng.logical_wire_bytes - l0,
                eng.cross_wire_bytes - c0)

    dl, dc = run_one(None, "m.budget.f32")
    local = 2                       # host_of_rank = [0, 0, 1, 1]
    assert dl > 0
    assert dc <= dl / local * 1.01 + 64, (dc, dl)
    dl8, dc8 = run_one("int8", "m.budget.int8")
    assert dc8 <= dc / 1.9, (dc8, dc)   # int16 partials ~halve the hop

    # a FLAT reduction on the same multi-host layout pays its whole
    # wire on the cross hop — the contrast the accounting exists for
    l0, c0 = eng.logical_wire_bytes, eng.cross_wire_bytes

    def fn_flat():
        x = np.ones(1 << 14, np.float32)
        hvd.allreduce(x, op=hvd.Sum, name="m.budget.flat")
        return True

    assert all(run_ranks(fn_flat))
    assert eng.cross_wire_bytes - c0 == eng.logical_wire_bytes - l0


def test_hierarchical_heterogeneous_host_slots_falls_back(live_engine):
    """3+1 host:slots layout: hierarchical cannot factor (the
    reference gates NCCLHierarchicalAllreduce on is_homogeneous the
    same way) — the request must silently run flat and stay exact."""
    from horovod_tpu.common import basics
    from horovod_tpu.common.topology import Topology

    eng = basics.engine()
    old = eng.topology
    eng.topology = Topology(size=NP, host_of_rank=[0, 0, 0, 1])
    try:
        flat0 = eng.algo_runs.get("flat", 0)
        hier0 = eng.algo_runs.get("hierarchical", 0)

        def fn():
            x = np.full(64, float(hvd.rank() + 1), np.float32)
            out = hvd.allreduce(x, op=hvd.Sum, name="m.hetero",
                                algorithm="hierarchical")
            np.testing.assert_array_equal(
                np.asarray(out), np.full(64, 10.0))
            return True

        assert all(run_ranks(fn))
        assert eng.algo_runs.get("flat", 0) > flat0
        assert eng.algo_runs.get("hierarchical", 0) == hier0
    finally:
        eng.topology = old


def test_compiled_torus_dp_tp_mesh_hint(live_engine):
    """dp x tp mesh torus case: an explicit TopologyHint pins the
    compiled decomposition to named axes, rides the cache key, and
    moves only 1/tp of the bytes across the dp (outer) axis."""
    def fn():
        r = hvd.rank()
        red = hvd.CompiledGroupedAllreduce(
            op=hvd.Sum, force_program=True, name="m.dp_tp",
            topology_hint=hvd.TopologyHint(axes=("dp", "tp"),
                                           sizes=(2, 2)))
        rng = np.random.default_rng(r)
        x = rng.standard_normal(512).astype(np.float32)
        out = red([x])[0]
        assert red.last_algorithm == "torus"
        assert red.last_cross_bytes * 2 == red.last_logical_bytes, \
            (red.last_cross_bytes, red.last_logical_bytes)
        return np.asarray(out, np.float64), x

    results = run_ranks(fn)
    expected = np.sum([x.astype(np.float64) for _, x in results],
                      axis=0)
    for out, _ in results:
        assert np.allclose(out, expected, atol=1e-5)


def test_torus_on_single_host(live_engine):
    """Torus needs no host map — a composite world size factors into
    the near-square grid (4 -> 2x2) even on one host, the arXiv
    1909.09756 2-D decomposition over one ICI domain."""
    from horovod_tpu.common import basics

    eng = basics.engine()
    t0 = eng.algo_runs.get("torus", 0)

    def fn():
        x = np.arange(130, dtype=np.float64) * (hvd.rank() + 1)
        out = hvd.allreduce(x, op=hvd.Sum, name="m.torus1h",
                            algorithm="torus")
        np.testing.assert_allclose(np.asarray(out),
                                   np.arange(130) * 10.0)
        return True

    assert all(run_ranks(fn))
    assert eng.algo_runs.get("torus", 0) > t0


def test_algorithm_mismatch_fails_loudly(live_engine):
    """Ranks disagreeing on the algorithm would issue different SPMD
    programs against each other — negotiation must reject, like a
    dtype mismatch."""
    from horovod_tpu.common.exceptions import TensorShapeMismatchError

    def fn():
        r = hvd.rank()
        algo = "torus" if r == 0 else "flat"
        x = np.ones(8, np.float32)
        try:
            hvd.allreduce(x, op=hvd.Sum, name="m.algomix",
                          algorithm=algo)
            return False
        except TensorShapeMismatchError:
            return True

    assert all(run_ranks(fn))


def test_pp_sched_mismatch_fails_loudly(live_engine):
    """Ranks running different pipeline schedules (or microbatch
    counts) would overlap different collectives into different
    bubbles and accumulate different gradient sums — the latched
    schedule@n_micro tag (Request.pp_sched, normally stamped by
    parallel/runtime.py on its bubble-overlapped reduces) must be
    cross-rank validated like the wire pair and algorithm.  The tag
    has no per-call API knob, so the divergent requests are built
    directly."""
    from horovod_tpu.common.exceptions import TensorShapeMismatchError
    from horovod_tpu.core.message import Request, RequestType
    from horovod_tpu.ops import api as ops_api

    def submit(name, tag):
        x = np.ones(8, np.float32)
        req = Request(
            request_type=RequestType.ALLREDUCE, tensor_name=name,
            rank=hvd.rank(), dtype=np.dtype(np.float32), shape=(8,),
            reduce_op=hvd.Sum, process_set_id=0, pp_sched=tag)
        return ops_api._submit(req, [x], [name])

    def fn():
        tag = "1f1b@4" if hvd.rank() == 0 else "gpipe@4"
        try:
            ops_api.synchronize(submit("m.ppmix", tag))
            return False
        except TensorShapeMismatchError as e:
            return "pipeline schedule" in str(e).lower()

    assert all(run_ranks(fn))

    # the agreeing case negotiates and executes normally
    def ok():
        out = ops_api.synchronize(submit("m.ppsame", "1f1b@4"))
        np.testing.assert_allclose(np.asarray(out), np.full(8, NP,
                                                            np.float32))
        return True

    assert all(run_ranks(ok))


def test_process_set_algorithm_decomposition(two_host_topology):
    """A sub-set spanning both hosts decomposes over ITS OWN rank
    list (ranks 1,2 live on different hosts but 1-per-host does not
    factor -> falls back flat and stays correct; the full-set
    hierarchical above proves the non-degenerate case)."""
    def fn():
        ps = hvd.add_process_set([1, 2])
        try:
            if hvd.rank() in (1, 2):
                x = np.ones(32, np.float32) * (hvd.rank() + 1)
                out = hvd.allreduce(x, op=hvd.Sum, process_set=ps,
                                    name="m.psalgo",
                                    algorithm="hierarchical")
                np.testing.assert_array_equal(np.asarray(out),
                                              np.full(32, 5.0))
            return True
        finally:
            hvd.remove_process_set(ps)

    assert all(run_ranks(fn))


# ---------------------------------------------------------------------------
# error-feedback convergence: a small LM trained over the int8 wire
# must reach the f32-wire loss (EF21: residuals cancel the
# quantization bias over steps instead of letting it accumulate)

def _train_tiny_lm(compression, steps=100):
    """Train next-token prediction of t -> (t + 1) % V on synthetic
    tokens, gradients averaged through DistributedOptimizer.  Returns
    the final loss (identical on every rank: grads are allreduced and
    weights start in sync)."""
    import torch
    import horovod_tpu.torch as thvd

    V, D, T, B = 32, 16, 8, 4

    def fn():
        r = hvd.rank()
        wrng = np.random.default_rng(0)
        emb = torch.nn.Parameter(torch.from_numpy(
            (wrng.standard_normal((V, D)) * 0.3).astype(np.float32)))
        head = torch.nn.Parameter(torch.from_numpy(
            (wrng.standard_normal((D, V)) * 0.3).astype(np.float32)))
        opt = torch.optim.SGD([emb, head], lr=1.0)
        opt = thvd.DistributedOptimizer(
            opt, named_parameters=[("emb", emb), ("head", head)],
            compression=compression)
        drng = np.random.default_rng(1000 + r)
        for _ in range(steps):
            x = torch.from_numpy(
                drng.integers(0, V, size=(B, T)).astype(np.int64))
            y = (x + 1) % V
            logits = emb[x] @ head
            loss = torch.nn.functional.cross_entropy(
                logits.reshape(-1, V), y.reshape(-1))
            opt.zero_grad()
            loss.backward()
            opt.step()
        # eval on a batch every rank shares: training data is sharded
        # per rank, so the train loss differs — the synced WEIGHTS are
        # what must agree
        erng = np.random.default_rng(42)
        with torch.no_grad():
            x = torch.from_numpy(
                erng.integers(0, V, size=(16, T)).astype(np.int64))
            y = (x + 1) % V
            eval_loss = torch.nn.functional.cross_entropy(
                (emb[x] @ head).reshape(-1, V), y.reshape(-1))
        return float(eval_loss)

    losses = run_ranks(fn)
    assert max(losses) - min(losses) < 1e-5, "ranks out of sync"
    return losses[0]


def test_int8_wire_error_feedback_convergence(live_engine):
    import horovod_tpu.torch as thvd

    f32_loss = _train_tiny_lm(thvd.Compression.none)
    int8_loss = _train_tiny_lm(thvd.Compression.int8)
    assert f32_loss < 1.0, f"baseline failed to learn: {f32_loss}"
    # acceptance bar: int8 wire with error feedback within 1% of the
    # f32-wire final loss
    assert abs(int8_loss - f32_loss) <= 0.01 * f32_loss + 1e-3, \
        (int8_loss, f32_loss)


def test_int4_wire_accounting(live_engine):
    """The int4 wire must show ~7.88x under f32 on the engine path
    (0.5 byte/elem packed nibbles + 2 bytes/256-elem block vs 4)."""
    from horovod_tpu.common import basics
    eng = basics.engine()
    l0, a0 = eng.logical_wire_bytes, eng.actual_wire_bytes
    q0 = eng.quantized_bucket_runs

    def fn():
        x = np.ones(1 << 16, np.float32)
        hvd.allreduce(x, op=hvd.Sum, name="m.acct4", wire_dtype="int4")
        return True

    assert all(run_ranks(fn))
    dl = eng.logical_wire_bytes - l0
    da = eng.actual_wire_bytes - a0
    assert eng.quantized_bucket_runs > q0
    assert dl > 0 and dl / da > 7.8, (dl, da)


# ---------------------------------------------------------------------------
# per-hop wire pair (ISSUE 9): (inner, outer) x algorithm x path —
# every cell must match the flat f32 reduction within the OUTER
# wire's tolerance (the inner 16-bit hop adds ~1e-2-scale error,
# absorbed by the quantized outer bounds; the pure-16-bit pairs use
# the fp16 bound)

PAIR_CASES = [
    (iw, ow, a, p)
    for iw, ow in ((None, "int8"), (None, "int4"), ("bf16", "int8"),
                   ("bf16", "int4"), ("bf16", None), ("fp16", "fp16"))
    for a in ("hierarchical", "torus")
    for p in ("engine", "compiled")
]


@pytest.mark.parametrize(
    "iw,ow,algo,path", PAIR_CASES,
    ids=[f"{iw or 'f32'}:{ow or 'f32'}-{a}-{p}"
         for iw, ow, a, p in PAIR_CASES])
def test_wire_pair_matrix(two_host_topology, iw, ow, algo, path):
    eng = two_host_topology
    runs0 = dict(eng.algo_runs)
    tag = f"{iw or 'f32'}.{ow or 'f32'}.{algo}.{path}"

    def fn():
        r = hvd.rank()
        rng = np.random.default_rng(r)
        x = rng.standard_normal(1000).astype(np.float32)
        if path == "compiled":
            out = hvd.compiled_allreduce(
                x, op=hvd.Sum, algorithm=algo,
                wire_dtype=ow or "f32", wire_inner=iw or "f32")
        else:
            out = hvd.allreduce(x, op=hvd.Sum, name=f"m.pair.{tag}",
                                algorithm=algo,
                                wire_dtype=ow or "f32",
                                wire_inner=iw or "f32")
        return np.asarray(out, np.float64), x

    results = run_ranks(fn)
    expected = np.sum([x.astype(np.float64) for _, x in results],
                      axis=0)
    # bf16 inner hops add their own rounding on top of the outer
    # wire's quantization error
    tol = WIRE_ATOL[ow] + (5e-2 if iw else 0.0)
    for out, _ in results:
        assert np.allclose(out, expected, atol=tol),             (iw, ow, algo, path, np.abs(out - expected).max())
    if path == "engine":
        assert eng.algo_runs.get(algo, 0) > runs0.get(algo, 0)


def _hop_bytes(hop):
    """Bytes the per-hop accounting has attributed to ``hop`` so far."""
    from horovod_tpu import telemetry

    fam = telemetry.metrics().get(telemetry.WIRE_HOP_BYTES_FAMILY, {})
    return sum(s.get("value", 0.0) for s in fam.get("samples", [])
               if s.get("labels", {}).get("hop") == hop)


def test_per_hop_cross_bytes_split(two_host_topology):
    """The hop accounting must show the pair's whole point: with pair
    (bf16, int4) on a hierarchical reduction, the inner hop moves
    2x the payload at bf16 width while the cross hop moves only the
    quantized 1/local_size shard — and the cross family's int4 bytes
    undercut the same reduction's int8 bytes."""
    def run_one(wire, name):
        i0, c0 = _hop_bytes("inner"), _hop_bytes("cross")

        def fn():
            x = np.ones(1 << 14, np.float32)
            hvd.allreduce(x, op=hvd.Sum, name=name,
                          algorithm="hierarchical", wire_dtype=wire,
                          wire_inner="bf16")
            return True

        assert all(run_ranks(fn))
        return (_hop_bytes("inner") - i0,
                _hop_bytes("cross") - c0)

    n = 1 << 14
    di8, dc8 = run_one("int8", "m.hop.i8")
    di4, dc4 = run_one("int4", "m.hop.i4")
    # inner hop: 2 passes (scatter + gather) at bf16 width
    assert di8 == di4 == 2 * n * 2, (di8, di4)
    # cross hop: int4 rides int8 partials at 2 hosts — half int8's
    # int16 partials
    assert 0 < dc4 < dc8, (dc4, dc8)
    assert dc8 <= n * 2 + 256, dc8       # int16 partials + scales
    assert dc4 <= n * 1 + 256, dc4       # int8 partials + scales


@pytest.mark.parametrize("inner,outer,hop,per_call", [
    ("f32", "int8", "cross", 2_105_344),   # int16 partials + scales
    ("f32", "int4", "cross", 1_056_768),   # int8 partials + scales
    ("bf16", "int4", "inner", 8_388_608),  # 2 passes at bf16 width
], ids=["f32:int8-cross", "f32:int4-cross", "bf16:int4-inner"])
def test_per_hop_bytes_of_an_8mib_call(two_host_topology, inner, outer,
                                       hop, per_call):
    """What each hop of the 2 x 2 decomposition moves for one 8 MiB
    call, to the byte: the counts ``tools/perf_gate.py`` holds the
    collective_bench leg to, read from the library directly."""
    before = _hop_bytes(hop)

    def fn():
        x = np.ones(1 << 21, np.float32)
        hvd.allreduce(x, op=hvd.Sum, name=f"m.hop8.{inner}.{outer}",
                      algorithm="torus", wire_dtype=outer,
                      wire_inner=inner)
        return True

    assert all(run_ranks(fn))
    assert _hop_bytes(hop) - before == per_call


def test_wire_inner_mismatch_fails_loudly(live_engine):
    """Ranks disagreeing on the inner-hop wire would issue different
    SPMD programs — negotiation must reject, like a dtype mismatch."""
    from horovod_tpu.common.exceptions import TensorShapeMismatchError

    def fn():
        r = hvd.rank()
        iw = "bf16" if r == 0 else "f32"
        x = np.ones(8, np.float32)
        try:
            hvd.allreduce(x, op=hvd.Sum, name="m.iwmix",
                          algorithm="torus", wire_dtype="int8",
                          wire_inner=iw)
            return False
        except TensorShapeMismatchError:
            return True

    assert all(run_ranks(fn))


def test_quantized_inner_wire_rejected(live_engine):
    """int8/int4 on the ICI hop is never legal — the API must reject
    it loudly (quantize.normalize_inner_wire), not silently degrade."""
    def fn():
        x = np.ones(8, np.float32)
        try:
            hvd.allreduce(x, op=hvd.Sum, name="m.badiw",
                          wire_inner="int4")
            return False
        except ValueError:
            return True

    assert all(run_ranks(fn))


def test_per_hop_ef_state_reset_on_resize(two_host_topology):
    """Satellite (ISSUE 9): per-hop EF residuals are DEVICE state
    keyed by executor — reset_wire_state() must drop them, and an
    executor swap (elastic resize) must purge the old mesh's entries
    so a post-resize step can never inject stale residual shapes.

    The rank threads share one engine, so every global mutation
    (state inspection, reset, executor swap, restore) runs on rank 0
    only, fenced by barriers — ranks racing their own swaps would
    rendezvous against different executors.  Barrier timeouts turn a
    rank-0 assertion failure into BrokenBarrierError on the peers
    instead of a deadlock."""
    import threading
    from horovod_tpu.common import basics
    from horovod_tpu.ops import compiled as comp

    bar = threading.Barrier(NP)
    shared = {}

    def fence():
        bar.wait(timeout=120)

    def fn():
        red = hvd.CompiledGroupedAllreduce(
            op=hvd.Sum, wire_dtype="int4", algorithm="torus",
            error_feedback=True, force_program=True, name="m.efreset")
        rng = np.random.default_rng(hvd.rank())
        x = rng.standard_normal(600).astype(np.float32)
        red([x])
        fence()
        if hvd.rank() == 0:
            with comp._EF_LOCK:
                n_state = len(comp._EF_STATE)
                shapes = [tuple(r.shape)
                          for v in comp._EF_STATE.values() for r in v]
            # the decomposed EF program materialized its sharded
            # residual
            assert n_state >= 1 and shapes, (n_state, shapes)
            # reset drops it (the elastic on_reset contract)
            red.reset_wire_state()
            with comp._EF_LOCK:
                assert not comp._EF_STATE
        fence()
        # run again, then simulate a resize: a NEW executor for the
        # same set must purge the old executor's entries on first use
        red([x])
        fence()
        if hvd.rank() == 0:
            eng = basics.engine()
            ps = eng.process_sets[0]
            with comp._EF_LOCK:
                shared["old_keys"] = set(comp._EF_STATE)
            assert shared["old_keys"]
            shared["ps"] = ps
            shared["old_ex"] = ps.executor
            ps.executor = eng._MeshExecutor(ps.executor.devices,
                                            ps.executor.num_ranks)
        fence()
        try:
            red([x])
            fence()
            if hvd.rank() == 0:
                with comp._EF_LOCK:
                    # old executor's residuals were purged; only the
                    # new mesh's state remains
                    assert not (shared["old_keys"]
                                & set(comp._EF_STATE))
                    assert comp._EF_STATE
        finally:
            fence()
            if hvd.rank() == 0:
                shared["ps"].executor = shared["old_ex"]
                comp.reset_ef_state()
        return True

    assert all(run_ranks(fn))


def test_int4_on_dcn_error_feedback_convergence(live_engine):
    """ISSUE 9 acceptance: the int4 wire ON THE CROSS-HOST HOP (per-
    hop pair via a hierarchical decomposition over a simulated 2-host
    layout) with error feedback converges within 1% of the f32-wire
    loss — the EF21 story extended to the narrowest wire format."""
    import horovod_tpu.torch as thvd
    from horovod_tpu.common import basics
    from horovod_tpu.common.topology import Topology

    eng = basics.engine()
    old_topo, old_algo = eng.topology, eng.config.algorithm
    f32_loss = _train_tiny_lm(thvd.Compression.none)
    eng.topology = Topology(size=NP, host_of_rank=[0, 0, 1, 1])
    eng.config.algorithm = "hierarchical"
    try:
        int4_loss = _train_tiny_lm(thvd.Compression.int4)
        # the decomposed path really ran (not a silent flat fallback)
        assert eng.algo_runs.get("hierarchical", 0) > 0
    finally:
        eng.topology, eng.config.algorithm = old_topo, old_algo
    assert f32_loss < 1.0, f"baseline failed to learn: {f32_loss}"
    assert abs(int4_loss - f32_loss) <= 0.01 * f32_loss + 1e-3, \
        (int4_loss, f32_loss)


# ---------------------------------------------------------------------------
# bucket-granular comm/compute overlap (the overlap PR): bucketized
# dispatch x wire pair x TopologyHint must match the one grouped
# program — BITWISE wherever the math is elementwise-equal (full
# width, 16-bit wires, and the flat quantized wire, whose bucket
# closure is BLOCK-aligned so every bucket's block grid coincides
# with the grouped buffer's), tight-allclose for quantized x hint
# (bucket boundaries are not hint-shard-aligned, documented in
# docs/concepts.md).

OVERLAP_WIRE_CASES = [
    # (wire, wire_inner, hint, bitwise)
    (None, None, False, True),
    ("bf16", None, False, True),
    ("fp16", None, False, True),
    ("int8", None, False, True),
    ("int4", None, False, True),
    (None, None, True, True),
    ("int8", "bf16", True, False),
]


@pytest.mark.parametrize(
    "wire,inner,hint,bitwise", OVERLAP_WIRE_CASES,
    ids=[f"{w or 'f32'}{'-' + i if i else ''}{'-hint' if h else ''}"
         for w, i, h, _ in OVERLAP_WIRE_CASES])
def test_bucketized_dispatch_matches_grouped(live_engine, wire, inner,
                                             hint, bitwise):
    tag = f"ov.{wire or 'f32'}.{inner or ''}.{int(hint)}"

    def run(bucket_bytes):
        def fn():
            th = hvd.TopologyHint(axes=("dp", "tp"), sizes=(2, 2)) \
                if hint else None
            red = hvd.CompiledGroupedAllreduce(
                op=hvd.Sum, wire_dtype=wire, wire_inner=inner,
                topology_hint=th, name=f"{tag}.{bucket_bytes}",
                bucket_bytes=bucket_bytes, force_program=True)
            rng = np.random.default_rng(hvd.rank())
            xs = [rng.standard_normal(600).astype(np.float32),
                  rng.standard_normal(1024).astype(np.float32),
                  rng.standard_normal(256).astype(np.float32)]
            outs = red(xs)
            return [np.asarray(o) for o in outs]
        return run_ranks(fn)

    grouped = run(0)
    bucketized = run(2048)       # splits the 1880-elem group
    for g_outs, b_outs in zip(grouped, bucketized):
        for g, b in zip(g_outs, b_outs):
            if bitwise:
                assert np.array_equal(g, b), \
                    (wire, inner, hint, np.abs(g - b).max())
            else:
                # quantized x hint: bucket block grids are their own
                # (align=1), so both dispatches sit within the codec
                # error bound of the true sum — and of each other
                assert np.allclose(g, b, atol=WIRE_ATOL[wire]), \
                    (wire, inner, hint, np.abs(g - b).max())


def test_bucketized_stream_incremental_push(live_engine):
    """push() in backward-completion order (reversed, like autograd
    produces grads) must give the same answer as the grouped call:
    push order decides WHEN a bucket launches, never WHICH bucket a
    tensor joins."""
    def fn():
        rng = np.random.default_rng(hvd.rank())
        xs = [rng.standard_normal(512).astype(np.float32)
              for _ in range(4)]
        red0 = hvd.CompiledGroupedAllreduce(
            op=hvd.Sum, name="ov.push.g", force_program=True)
        want = [np.asarray(o) for o in red0(xs)]
        red = hvd.CompiledGroupedAllreduce(
            op=hvd.Sum, name="ov.push.b", bucket_bytes=1024,
            force_program=True)
        st = red.stream([(x.shape, x.dtype) for x in xs])
        for i in reversed(range(4)):
            st.push(i, xs[i])
        got = [np.asarray(o) for o in st.result()]
        for w, g in zip(want, got):
            assert np.array_equal(w, g)
        return True

    assert all(run_ranks(fn))


def test_bucketized_zero_steady_state_recompiles(live_engine):
    """After the first step compiles each bucket program, later steps
    must be pure cache hits — the zero-recompile invariant carries
    over to bucket granularity (equal-shaped buckets even share one
    program via the miniplan signature)."""
    from horovod_tpu import telemetry

    def fn():
        rng = np.random.default_rng(hvd.rank())
        xs = [rng.standard_normal(512).astype(np.float32)
              for _ in range(4)]
        red = hvd.CompiledGroupedAllreduce(
            op=hvd.Sum, name="ov.steady", bucket_bytes=1024,
            force_program=True)
        red(xs)                      # warm: compiles bucket programs
        m0 = telemetry.counter_total(
            telemetry.PROGRAM_CACHE_MISSES_FAMILY)
        b0 = telemetry.counter_total(telemetry.OVERLAP_BUCKETS_FAMILY)
        for _ in range(3):
            red(xs)
        misses = telemetry.counter_total(
            telemetry.PROGRAM_CACHE_MISSES_FAMILY) - m0
        buckets = telemetry.counter_total(
            telemetry.OVERLAP_BUCKETS_FAMILY) - b0
        return misses, buckets

    for misses, buckets in run_ranks(fn):
        assert misses == 0, misses       # zero steady-state recompiles
        # 4 buckets per step (each 2 KiB tensor tops the 1 KiB
        # ceiling); the counter is process-global across the rank
        # threads, so this rank sees AT LEAST its own 3 steps' worth
        assert buckets >= 3 * 4, buckets


def test_bucketized_exposed_comm_telemetry(live_engine):
    """Both dispatch paths land wall seconds in the exposed-comm
    counter under their own path label — the number the overlap gate
    (ci.sh perf) diffs."""
    from horovod_tpu import telemetry

    def fn():
        rng = np.random.default_rng(hvd.rank())
        xs = [rng.standard_normal(512).astype(np.float32)
              for _ in range(2)]
        reg = telemetry.registry()
        fam = telemetry.EXPOSED_COMM_SECONDS_FAMILY
        c = reg.counter(fam, telemetry.EXPOSED_COMM_SECONDS_HELP,
                        labelnames=telemetry.EXPOSED_COMM_SECONDS_LABELS)
        path_grouped, path_bucketized = "grouped", "bucketized"
        g0 = c.labels(path=path_grouped).value
        b0 = c.labels(path=path_bucketized).value
        hvd.CompiledGroupedAllreduce(
            op=hvd.Sum, name="ov.tele.g", force_program=True)(xs)
        hvd.CompiledGroupedAllreduce(
            op=hvd.Sum, name="ov.tele.b", bucket_bytes=1024,
            force_program=True)(xs)
        return (c.labels(path=path_grouped).value - g0,
                c.labels(path=path_bucketized).value - b0)

    for dg, db in run_ranks(fn):
        assert dg > 0 and db > 0, (dg, db)


def test_bucketized_per_bucket_integrity_digests(live_engine):
    """Every bucket launch arms its own wire digest: a bucketized
    step must raise the ok-verification counter by (buckets) per
    step, not once — PR 15's end-to-end integrity at bucket grain."""
    from horovod_tpu import telemetry

    def fn():
        from horovod_tpu.common import basics
        eng = basics.engine()
        if not getattr(eng.config, "integrity_checks", True):
            return None
        rng = np.random.default_rng(hvd.rank())
        xs = [rng.standard_normal(512).astype(np.float32)
              for _ in range(4)]
        red = hvd.CompiledGroupedAllreduce(
            op=hvd.Sum, name="ov.integrity", bucket_bytes=1024,
            force_program=True)
        red(xs)                                    # warm
        k0 = telemetry.counter_total(
            telemetry.INTEGRITY_CHECKS_FAMILY)
        red(xs)
        return telemetry.counter_total(
            telemetry.INTEGRITY_CHECKS_FAMILY) - k0

    deltas = [d for d in run_ranks(fn) if d is not None]
    # 2 buckets/step, each verified on this rank's local positions
    assert deltas and all(d >= 2 for d in deltas), deltas


def test_bucketized_relatch_cannot_split_one_step(live_engine):
    """The stream latches bucket_bytes at construction: an autotuner
    flip mid-step (between pushes) must not re-bucketize the step in
    flight — the next stream picks the new ceiling up instead."""
    def fn():
        from horovod_tpu.common import basics
        eng = basics.engine()
        rng = np.random.default_rng(hvd.rank())
        xs = [rng.standard_normal(512).astype(np.float32)
              for _ in range(4)]
        red = hvd.CompiledGroupedAllreduce(
            op=hvd.Sum, name="ov.latch", force_program=True)
        old = eng.config.overlap_bucket_bytes
        eng.config.overlap_bucket_bytes = 1024
        try:
            st = red.stream([(x.shape, x.dtype) for x in xs])
            assert st.bucket_bytes == 1024
            st.push(0, xs[0])
            # the flip lands between pushes — this step keeps its
            # latched bucketing...
            eng.config.overlap_bucket_bytes = 0
            for i in range(1, 4):
                st.push(i, xs[i])
            st.result()
            assert st.bucket_bytes == 1024
            # each 2 KiB tensor tops the 1 KiB ceiling by itself
            assert len(st.buckets) == 4
            # ...and the NEXT stream re-latches the new value
            st2 = red.stream([(x.shape, x.dtype) for x in xs])
            assert st2.bucket_bytes == 0
            for i in range(4):
                st2.push(i, xs[i])
            st2.result()
        finally:
            eng.config.overlap_bucket_bytes = old
        return True

    assert all(run_ranks(fn))


# ---------------------------------------------------------------------------
# fused quantized alltoall: wire x path x TopologyHint.  The MoE
# dispatch wire must round-trip within its codec's tolerance on BOTH
# dispatch paths, under a flat layout and under an explicit dp x tp
# hint, and the quantized formats must show their honest byte
# reduction in the alltoall accounting families.

A2A_WIRE_CASES = (
    [("engine", w, "flat") for w in ("f32", "bf16", "fp16",
                                     "int8", "int4")]
    + [("compiled", w, h) for w in ("f32", "bf16", "fp16",
                                    "int8", "int4")
       for h in ("flat", "torus")]
)


def _a2a_tol(wire, absmax):
    if wire == "f32":
        return 0.0
    if wire == "bf16":
        return absmax / 128.0
    if wire == "fp16":
        return absmax / 1024.0
    if wire == "int8":
        # scale = absmax/127 (bf16-roundtripped), worst case half a
        # step plus the scale's own bf16 roundoff
        return absmax / 127.0
    return absmax / 7.0  # int4: qmax 7


@pytest.mark.parametrize("path,wire,hint", A2A_WIRE_CASES,
                         ids=[f"{p}-{w}-{h}"
                              for p, w, h in A2A_WIRE_CASES])
def test_alltoall_wire_matrix(live_engine, path, wire, hint):
    seg = 512  # whole scale blocks per (rank, dest) slot

    def fn():
        r = hvd.rank()
        base = np.linspace(-1.0, 1.0, NP * seg).astype(np.float32)
        x = base + 0.25 * r
        if path == "engine":
            out, _recv = hvd.alltoall(
                x, wire_dtype=wire, error_feedback=False,
                name=f"m.a2aw.{wire}")
        else:
            th = hvd.TopologyHint(axes=("dp", "tp"), sizes=(2, 2)) \
                if hint == "torus" else None
            out = hvd.compiled_alltoall(
                x, wire_dtype=wire, topology_hint=th,
                name=f"m.a2aw.{wire}.{hint}")
        expected = np.concatenate(
            [base[r * seg:(r + 1) * seg] + 0.25 * p
             for p in range(NP)])
        tol = _a2a_tol(wire, float(np.abs(x).max()))
        err = float(np.abs(np.asarray(out, np.float64)
                           - expected).max())
        assert err <= tol + 1e-6, (wire, err, tol)
        return True

    assert all(run_ranks(fn))


@pytest.mark.parametrize("path", ["engine", "compiled"])
@pytest.mark.parametrize("wire,ratio", [("int8", 3.969), ("int4", 7.877)])
def test_alltoall_quantized_accounting(live_engine, path, wire, ratio):
    """The alltoall byte families must show the codec's true wire
    reduction — 1024 bytes of f32 per 256-element block over 256 (int8)
    or 128 (int4) code bytes and a 2-byte scale — on both dispatch
    paths (the exchange ships codes + scales, never dequantized f32)."""
    from horovod_tpu import telemetry
    l0 = telemetry.counter_total(telemetry.ALLTOALL_LOGICAL_BYTES_FAMILY)
    a0 = telemetry.counter_total(telemetry.ALLTOALL_WIRE_BYTES_FAMILY)

    def fn():
        x = np.linspace(-1.0, 1.0, NP * 512).astype(np.float32)
        if path == "engine":
            hvd.alltoall(x, wire_dtype=wire, name=f"m.a2acct.{wire}")
        else:
            hvd.compiled_alltoall(x, wire_dtype=wire,
                                  name=f"m.a2acct.{wire}")
        return True

    assert all(run_ranks(fn))
    dl = telemetry.counter_total(
        telemetry.ALLTOALL_LOGICAL_BYTES_FAMILY) - l0
    da = telemetry.counter_total(
        telemetry.ALLTOALL_WIRE_BYTES_FAMILY) - a0
    assert dl > 0 and round(dl / da, 3) == ratio, (dl, da, dl / da)


def test_compiled_alltoall_single_program(live_engine):
    """The compiled alltoall is ONE cached program per (executor,
    signature) — steady-state steps are pure cache hits with zero
    recompiles, across every local rank thread."""
    from horovod_tpu import telemetry

    def fn():
        a2a = hvd.CompiledAlltoall(name="m.a2a.single",
                                   wire_dtype="int8",
                                   force_program=True)
        x = np.linspace(-1.0, 1.0, NP * 512).astype(np.float32)
        a2a(x)                       # warm: compiles the program
        m0 = telemetry.counter_total(
            telemetry.PROGRAM_CACHE_MISSES_FAMILY)
        for _ in range(3):
            a2a(x)
        misses = telemetry.counter_total(
            telemetry.PROGRAM_CACHE_MISSES_FAMILY) - m0
        return misses, len(a2a._programs)

    for misses, n_prog in run_ranks(fn):
        assert misses == 0, misses   # zero steady-state recompiles
        assert n_prog == 1, n_prog


def test_alltoall_ragged_rejected_on_compiled_path(live_engine):
    """Ragged exchanges belong to the negotiated engine path — the
    compiled program bakes equal splits into its shape signature."""
    def fn():
        a2a = hvd.CompiledAlltoall(name="m.a2a.ragged")
        with pytest.raises(ValueError, match="hvd.alltoall"):
            a2a(np.ones(NP * 8 + 1, np.float32))
        return True

    assert all(run_ranks(fn))
