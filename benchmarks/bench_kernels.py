"""Kernel-level fused-vs-unfused microbenchmarks.

For each fusion the tentpole added, time the fused kernel against the
exact unfused pipeline it replaces (same blocks, same dtypes, same
dispatch layer), so the ``kernels/*`` rows in bench.csv quantify what
the fusion buys:

  gather_spmm    fused table indirection  vs  materialize h[table] +
                 plain spmm (the (U, D) HBM round-trip the §3.5 fusion
                 removes);
  gat_attention  one-pass SDDMM+softmax over all heads  vs  per-head
                 sddmm calls + stack/scale + masked softmax (the (N, F)
                 score round-trip).

Every kernel runs at its default blocks (row block ``auto_block_n``,
128-lane feature block), the blocks ``PallasExecutor`` passes.  Off-TPU
the kernels run in interpret mode, so absolute numbers are emulation
speed; the fused-vs-unfused ratio is still the artifact.
"""
import numpy as np

from benchmarks.common import emit, time_fn

FANOUT = 16


def _world(rng, N, U, D, F, dtype):
    import jax.numpy as jnp
    h = jnp.asarray(rng.standard_normal((U, D)), dtype)
    table = jnp.asarray(rng.permutation(U), jnp.int32)
    w = jnp.asarray(rng.standard_normal((N, F)), dtype)
    nbr = jnp.asarray(rng.integers(0, U, (N, F)), jnp.int32)
    mask = jnp.asarray(rng.random((N, F)) > 0.25)
    return h, table, w, nbr, mask


def _bench_gather_spmm(N, D, F, iters):
    import jax.numpy as jnp

    from repro.kernels import ops as kops

    rng = np.random.default_rng(0)
    h, table, w, nbr, mask = _world(rng, N, N, D, F, jnp.float32)

    def fused():
        return kops.gather_spmm(h, table, w, nbr, mask, use_kernel=True)

    def unfused():
        return kops.spmm(jnp.take(h, table, axis=0), w, nbr, mask,
                         use_kernel=True)

    from repro import obs
    with obs.span("kernels.gather_spmm") as sp:
        t_f = time_fn(fused, iters=iters)
        t_u = time_fn(unfused, iters=iters)
        if sp:
            sp.set(n=N, fused_us=t_f * 1e6, unfused_us=t_u * 1e6)
    emit(f"kernels/gather_spmm/n{N}", t_f * 1e6,
         f"unfused_us={t_u * 1e6:.1f};speedup={t_u / t_f:.2f}x")
    np.testing.assert_array_equal(np.asarray(fused()),
                                  np.asarray(unfused()))


def _bench_gat_attention(N, D, F, heads, iters):
    import jax.numpy as jnp

    from repro.core.gnn_models import masked_softmax
    from repro.kernels import ops as kops

    rng = np.random.default_rng(1)
    q, _, _, nbr, mask = _world(rng, N, N, D, F, jnp.float32)
    k = jnp.asarray(rng.standard_normal((N, D)), jnp.float32)
    dh = D // heads

    def fused():
        return kops.gat_attention(q, k, nbr, mask, heads=heads,
                                  use_kernel=True)

    def unfused():
        # the pre-fusion pipeline: one sddmm kernel per head, stack,
        # scale, then a separate masked-softmax pass over the scores
        per_head = [kops.sddmm(q[:, h * dh:(h + 1) * dh],
                               k[:, h * dh:(h + 1) * dh], nbr, mask,
                               use_kernel=True)
                    for h in range(heads)]
        s = jnp.stack(per_head, axis=-1) / jnp.sqrt(jnp.float32(dh))
        alpha = masked_softmax(s.transpose(0, 2, 1),
                               mask[:, None, :]).transpose(0, 2, 1)
        return alpha * mask[:, :, None]

    from repro import obs
    with obs.span("kernels.gat_attention") as sp:
        t_f = time_fn(fused, iters=iters)
        t_u = time_fn(unfused, iters=iters)
        if sp:
            sp.set(n=N, heads=heads, fused_us=t_f * 1e6,
                   unfused_us=t_u * 1e6)
    emit(f"kernels/gat_attention/n{N}/h{heads}", t_f * 1e6,
         f"unfused_us={t_u * 1e6:.1f};speedup={t_u / t_f:.2f}x")
    np.testing.assert_allclose(np.asarray(fused()), np.asarray(unfused()),
                               atol=2e-5, rtol=3e-3)


def run(smoke: bool = False):
    iters = 1 if smoke else 3
    heads = 4
    shapes = [(256, 64)] if smoke else [(256, 64), (1024, 128)]
    for N, D in shapes:
        _bench_gather_spmm(N, D, FANOUT, iters)
        _bench_gat_attention(N, D, FANOUT, heads, iters)


if __name__ == "__main__":
    import pathlib
    import sys
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]
                           / "src"))
    run()
