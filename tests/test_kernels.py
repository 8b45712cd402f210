"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref oracle.

Plus the fused-kernel acceptance tests (gather+SPMM, SDDMM+softmax):
random-data sweeps at the standard tolerances AND strict <5e-7 f32
checks on mantissa-quantized inputs, where every reduction is exact in
any association order — so kernel-vs-oracle differences must be ZERO,
not merely small.  And the block autotuner round-trip."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gat_attention import gat_attention
from repro.kernels.gather_spmm import gather_spmm
from repro.kernels.sddmm import sddmm
from repro.kernels.spmm import spmm

ATOL = {jnp.float32: 2e-5, jnp.bfloat16: 3e-2}


def _quantized(rng, shape, step=2 ** -6, span=32):
    """f32 values on a coarse mantissa lattice (multiples of ``step``,
    small magnitude): short sums of them are EXACT in any association
    order, so fused vs oracle must agree bitwise."""
    return (rng.integers(-span, span, shape) * step).astype(np.float32)


@pytest.mark.parametrize("N,D,F,bn,bd", [
    (16, 128, 4, 8, 128),
    (32, 256, 8, 8, 128),
    (64, 128, 16, 16, 64),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_spmm_sweep(N, D, F, bn, bd, dtype, rng):
    h = jnp.asarray(rng.standard_normal((N, D)), dtype)
    w = jnp.asarray(rng.standard_normal((N, F)), dtype)
    nbr = jnp.asarray(rng.integers(0, N, (N, F)), jnp.int32)
    mask = jnp.asarray(rng.random((N, F)) > 0.25)
    got = spmm(h, w, nbr, mask, block_n=bn, block_d=bd, interpret=True)
    want = ref.spmm_ref(h, w, nbr, mask)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=ATOL[dtype] * F, rtol=3e-2)


@pytest.mark.parametrize("N,D,F", [(16, 64, 4), (32, 128, 8), (24, 96, 6)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sddmm_sweep(N, D, F, dtype, rng):
    q = jnp.asarray(rng.standard_normal((N, D)), dtype)
    k = jnp.asarray(rng.standard_normal((N, D)), dtype)
    nbr = jnp.asarray(rng.integers(0, N, (N, F)), jnp.int32)
    mask = jnp.asarray(rng.random((N, F)) > 0.25)
    got = sddmm(q, k, nbr, mask, block_n=8, interpret=True)
    want = ref.sddmm_ref(q, k, nbr, mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=ATOL[dtype] * np.sqrt(D), rtol=3e-2)


@pytest.mark.parametrize("BH,S,hd,bq,bk", [
    (2, 128, 64, 64, 64),
    (4, 256, 64, 128, 128),
    (2, 128, 128, 32, 64),
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_sweep(BH, S, hd, bq, bk, causal, dtype, rng):
    q = jnp.asarray(rng.standard_normal((BH, S, hd)), dtype)
    k = jnp.asarray(rng.standard_normal((BH, S, hd)), dtype)
    v = jnp.asarray(rng.standard_normal((BH, S, hd)), dtype)
    got = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=ATOL[dtype], rtol=3e-2)


# ----------------------------------------------------------------------
# fused index-gather + SPMM
# ----------------------------------------------------------------------

@pytest.mark.parametrize("R,U,D,F,bn,bd", [
    (16, 16, 128, 4, 8, 128),       # square geometry
    (32, 48, 256, 8, 8, 128),       # subset: more table rows than outputs
    (64, 80, 96, 16, 16, 32),       # delta-shaped, non-pow2 D
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gather_spmm_sweep(R, U, D, F, bn, bd, dtype, rng):
    h = jnp.asarray(rng.standard_normal((U, D)), dtype)
    table = jnp.asarray(rng.permutation(U), jnp.int32)
    w = jnp.asarray(rng.standard_normal((R, F)), dtype)
    nbr = jnp.asarray(rng.integers(0, U, (R, F)), jnp.int32)
    mask = jnp.asarray(rng.random((R, F)) > 0.25)
    got = gather_spmm(h, table, w, nbr, mask, block_n=bn, block_d=bd,
                      interpret=True)
    want = ref.gather_spmm_ref(h, table, w, nbr, mask)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=ATOL[dtype] * F, rtol=3e-2)


def test_gather_spmm_bitwise_vs_materialized(rng):
    """The fused indirection must equal the materialized reorder BITWISE:
    spmm over h[table] sees the same values in the same per-row order."""
    R, U, D, F = 32, 40, 128, 8
    h = jnp.asarray(rng.standard_normal((U, D)).astype(np.float32))
    table = jnp.asarray(rng.permutation(U), jnp.int32)
    w = jnp.asarray(rng.standard_normal((R, F)).astype(np.float32))
    nbr = jnp.asarray(rng.integers(0, U, (R, F)), jnp.int32)
    mask = jnp.asarray(rng.random((R, F)) > 0.25)
    fused = gather_spmm(h, table, w, nbr, mask, block_n=8, block_d=128,
                        interpret=True)
    materialized = spmm(jnp.take(h, table, axis=0), w, nbr, mask,
                        block_n=8, block_d=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(fused),
                                  np.asarray(materialized))


def test_gather_spmm_quantized_strict(rng):
    """Acceptance gate: f32 max err < 5e-7 vs the oracle.  On the
    quantized lattice the sums are exact, so this is really 0.0."""
    R, U, D, F = 64, 96, 128, 16
    h = jnp.asarray(_quantized(rng, (U, D)))
    table = jnp.asarray(rng.permutation(U), jnp.int32)
    w = jnp.asarray(_quantized(rng, (R, F)))
    nbr = jnp.asarray(rng.integers(0, U, (R, F)), jnp.int32)
    mask = jnp.asarray(rng.random((R, F)) > 0.25)
    got = np.asarray(gather_spmm(h, table, w, nbr, mask, interpret=True))
    want = np.asarray(ref.gather_spmm_ref(h, table, w, nbr, mask))
    assert np.abs(got - want).max() < 5e-7


# ----------------------------------------------------------------------
# fused SDDMM + masked softmax (GAT attention)
# ----------------------------------------------------------------------

@pytest.mark.parametrize("N,U,D,F,heads", [
    (16, 16, 64, 4, 1),
    (32, 48, 64, 8, 4),             # subset geometry: U > N
    (64, 64, 128, 16, 8),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gat_attention_sweep(N, U, D, F, heads, dtype, rng):
    q = jnp.asarray(rng.standard_normal((N, D)), dtype)
    k = jnp.asarray(rng.standard_normal((U, D)), dtype)
    nbr = jnp.asarray(rng.integers(0, U, (N, F)), jnp.int32)
    mask = jnp.asarray(rng.random((N, F)) > 0.25)
    got = gat_attention(q, k, nbr, mask, heads=heads, interpret=True)
    want = ref.gat_attention_ref(q, k, nbr, mask, heads)
    assert got.shape == (N, F, heads)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=ATOL[dtype], rtol=3e-2)
    # masked slots are exactly zero and unmasked rows sum to 1 per head
    got_np = np.asarray(got)
    assert (got_np[~np.asarray(mask)] == 0.0).all()


def test_gat_attention_strict_f32(rng):
    """Acceptance gate: fused attention within 5e-7 of the oracle on
    random f32 data (softmax normalizes, so the dot rounding washes)."""
    N, U, D, F, heads = 64, 96, 128, 16, 4
    q = jnp.asarray(rng.standard_normal((N, D)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((U, D)).astype(np.float32))
    nbr = jnp.asarray(rng.integers(0, U, (N, F)), jnp.int32)
    mask = jnp.asarray(rng.random((N, F)) > 0.25)
    got = np.asarray(gat_attention(q, k, nbr, mask, heads=heads,
                                  interpret=True))
    want = np.asarray(ref.gat_attention_ref(q, k, nbr, mask, heads))
    assert np.abs(got - want).max() < 5e-7


# ----------------------------------------------------------------------
# executor integration: fused paths on non-aligned shapes
# ----------------------------------------------------------------------

def _dense_io(rng, R, U, F, table=True):
    from repro.core.ops import DenseIO
    nbr = rng.integers(0, U, (R, F)).astype(np.int32)
    mask = rng.random((R, F)) > 0.25
    tbl = rng.permutation(U).astype(np.int32) if table else None
    return DenseIO(nbr, mask, table=tbl)


def test_executor_fused_gather_non_aligned_strict(rng):
    """PallasExecutor's fused-gather spmm on awkward shapes (R not a
    block multiple, D needing column padding) vs the ref executor over
    the SAME io — quantized inputs, so < 5e-7 means exact."""
    from repro.core.ops import PallasExecutor, RefExecutor
    R, U, D, F = 23, 37, 20, 6
    io = _dense_io(rng, R, U, F)
    h = jnp.asarray(_quantized(rng, (U, D)))
    got = np.asarray(PallasExecutor(use_kernel=True).spmm(h, io.mean_w, io))
    want = np.asarray(RefExecutor().spmm(h, io.mean_w, io))
    assert got.shape == (R, D)
    assert np.abs(got - want).max() < 5e-7


def test_executor_fused_gather_matches_unfused(rng):
    """fused_gather=False resolves the table eagerly; both routes must
    produce identical bits."""
    from repro.core.ops import PallasExecutor
    R, U, D, F = 50, 61, 32, 8
    io = _dense_io(rng, R, U, F)
    h = jnp.asarray(rng.standard_normal((U, D)).astype(np.float32))
    fused = PallasExecutor(use_kernel=True, fused_gather=True)
    unfused = PallasExecutor(use_kernel=True, fused_gather=False)
    np.testing.assert_array_equal(
        np.asarray(fused.spmm(h, io.mean_w, io)),
        np.asarray(unfused.spmm(h, io.mean_w, io)))


@pytest.mark.parametrize("N,D,heads", [(50, 32, 4), (64, 64, 1)])
def test_executor_fused_attention_layer(N, D, heads, rng):
    """A full GAT layer through ``run_layer``: the peephole must fire on
    the fused executor, agree tightly with the unfused kernel path, and
    match the jnp oracle within the standard tolerance."""
    import jax

    from repro.core.gnn_models import init_gat, model_spec
    from repro.core.ops import (DenseIO, PallasExecutor, RefExecutor,
                                run_layer)
    F = 6
    spec = model_spec("gat", init_gat(jax.random.PRNGKey(0), [D, D],
                                      heads=heads))
    io = _dense_io(rng, N, N, F, table=False)
    H = jnp.asarray(rng.standard_normal((N, D)).astype(np.float32))

    fused_ex = PallasExecutor(use_kernel=True, fused_attention=True)
    unfused_ex = PallasExecutor(use_kernel=True, fused_attention=False)
    assert fused_ex.attn_scores_softmax is not None
    assert unfused_ex.attn_scores_softmax is None

    layer = spec.layers[0]
    got = np.asarray(run_layer(fused_ex, layer, io, H, H, heads))
    unfused = np.asarray(run_layer(unfused_ex, layer, io, H, H, heads))
    want = np.asarray(run_layer(RefExecutor(), layer, io, H, H, heads))
    np.testing.assert_allclose(got, unfused, atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=3e-3)


# ----------------------------------------------------------------------
# block-size autotuner
# ----------------------------------------------------------------------

def test_autotune_roundtrip(tmp_path, monkeypatch):
    """ensure_tuned searches the candidate grid once (injected timer),
    persists the winner, serves later calls from the file, and re-runs
    only under REPRO_TUNING=autotune."""
    from repro import tuning
    monkeypatch.delenv("REPRO_TUNING", raising=False)
    path = tmp_path / "blocks.json"
    table = tuning.BlockTable(path=path)
    current, seen = {}, []

    def make_call(blocks):
        def fn():
            current.clear()
            current.update(blocks)
        return fn

    def timer(fn, repeats):
        fn()
        seen.append(dict(current))
        return abs(current["block_n"] - 32) + 1.0   # 32 always wins

    blocks = tuning.ensure_tuned(table, "sddmm", make_call, N=100,
                                 timer=timer)
    assert blocks == {"block_n": 32}
    assert path.exists() and seen     # searched and persisted
    # every block_n candidate that tiles the n128 bucket was tried
    assert sorted(c["block_n"] for c in seen) == [8, 16, 32, 64]

    # a fresh load serves the whole shape bucket without re-searching
    t2 = tuning.BlockTable.load(path)
    n_calls = len(seen)
    assert tuning.ensure_tuned(t2, "sddmm", make_call, N=100,
                               timer=timer) == {"block_n": 32}
    assert tuning.ensure_tuned(t2, "sddmm", make_call, N=128,
                               timer=timer) == {"block_n": 32}
    assert len(seen) == n_calls
    got = t2.lookup("sddmm", N=100)
    assert got == {"block_n": 32}     # the `us` field stays out of lookup

    # forcing invalidates the persisted winner
    monkeypatch.setenv("REPRO_TUNING", "autotune")
    assert tuning.autotune_forced()
    tuning.ensure_tuned(t2, "sddmm", make_call, N=100, timer=timer)
    assert len(seen) > n_calls


def test_executor_consults_block_table(rng):
    """A bound BlockTable overrides the constructor blocks at bind time,
    and tuned vs default blocks are bitwise-identical (block sizes never
    change the per-row accumulation order)."""
    from repro import tuning
    from repro.core.ops import PallasExecutor
    N, U, D, F = 64, 64, 128, 8
    tb = tuning.BlockTable()
    tb.put("gather_spmm", N=N, D=D, blocks={"block_n": 16, "block_d": 128})
    ex = PallasExecutor(use_kernel=True, block_table=tb)
    assert ex._pick_blocks("gather_spmm", N, D, jnp.float32) == (16, 128)
    assert ex._pick_blocks("spmm", N, D, jnp.float32) == (None, 128)

    io = _dense_io(rng, N, U, F)
    h = jnp.asarray(rng.standard_normal((U, D)).astype(np.float32))
    got = np.asarray(ex.spmm(h, io.mean_w, io))
    base = np.asarray(PallasExecutor(use_kernel=True).spmm(h, io.mean_w,
                                                           io))
    np.testing.assert_array_equal(got, base)


def test_auto_block_n_defaults():
    """The satellite fix: sddmm no longer hard-defaults to block_n=8 —
    both kernels take the largest divisor <= 64 of the row count."""
    from repro.kernels.spmm import auto_block_n
    assert auto_block_n(256) == 64
    assert auto_block_n(24) == 8
    assert auto_block_n(20) == 4
    assert auto_block_n(7) == 1


def test_flash_matches_model_attention(rng):
    """The Pallas kernel and the model's jnp flash agree."""
    from repro.models.attention import flash_attention_jnp
    BH, S, hd = 2, 128, 64
    q = jnp.asarray(rng.standard_normal((BH, S, hd)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((BH, S, hd)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((BH, S, hd)).astype(np.float32))
    got = flash_attention(q, k, v, causal=True)
    want = flash_attention_jnp(q[:, :, None], k[:, :, None], v[:, :, None],
                               causal=True, q_block=64, kv_block=64)[:, :, 0]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=3e-5, rtol=3e-5)
