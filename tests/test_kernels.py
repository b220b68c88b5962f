"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps
+ hypothesis property tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _propcheck import given, settings, st

from repro.core import mesh2d, mesh2d_edge_io, torus, traffic
from repro.core.nrank import possibility_weights as possibility_oracle
from repro.kernels.possibility import ops as poss_ops
from repro.kernels.flash_attention import ops as flash_ops
from repro.kernels.flash_attention.ref import flash_attention as flash_ref
from repro.kernels.mamba_scan import ops as scan_ops
from repro.kernels.mamba_scan.ref import selective_scan as scan_ref


# --------------------------------------------------------------------- #
# possibility weights (N-Rank hot spot)
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("topo_fn,pattern", [
    (lambda: mesh2d(5, 5), "uniform"),
    (lambda: mesh2d_edge_io(5, 5), "overturn"),
    (lambda: torus(8, 8), "uniform"),
    (lambda: mesh2d(4, 7), "shuffle"),
])
def test_possibility_kernel_matches_core_oracle(topo_fn, pattern):
    """Defaults = the compiled path for the current backend (dense jnp on
    CPU, compiled Pallas on TPU/GPU) — never the interpreter."""
    topo = topo_fn()
    t = traffic.PATTERNS[pattern](topo)
    w_ref, wd_ref = possibility_oracle(topo.distances, t, topo.channels)
    w, wd = poss_ops.possibility_weights(topo.distances, t, topo.channels)
    np.testing.assert_allclose(np.asarray(w), w_ref, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(wd), wd_ref, rtol=1e-5, atol=1e-7)


def test_possibility_pallas_kernel_itself_matches_oracle():
    """The Pallas kernel proper (interpret mode where it cannot compile,
    e.g. CPU CI) against the numpy oracle, both offsets."""
    interpret = not poss_ops.backend_supports_pallas()
    topo = torus(8, 8)
    t = traffic.uniform(topo)
    w_ref, wd_ref = possibility_oracle(topo.distances, t, topo.channels)
    w, wd = poss_ops.possibility_weights(topo.distances, t, topo.channels,
                                         use_pallas=True,
                                         interpret=interpret)
    np.testing.assert_allclose(np.asarray(w), w_ref, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(wd), wd_ref, rtol=1e-5, atol=1e-7)
    # offset=2: the consecutive-pair predicate on (u, n2) index pairs
    from repro.core.nrank import joint_possibility
    j = joint_possibility(topo, t)
    chans = topo.channels
    pairs = np.argwhere(j > 0)
    ab = np.stack([chans[pairs[:, 0], 0], chans[pairs[:, 1], 1]], axis=1)
    w2, _ = poss_ops.possibility_weights(topo.distances, t, ab,
                                         use_pallas=True,
                                         interpret=interpret, offset=2)
    np.testing.assert_allclose(np.asarray(w2), j[pairs[:, 0], pairs[:, 1]],
                               rtol=1e-5, atol=1e-7)


def test_possibility_v_pallas_matches_dense():
    """The per-destination V kernel feeding the fused planner: row sums
    are eq. 5, the d = n gather is eq. 7."""
    from repro.kernels.possibility.kernel import possibility_v_pallas
    from repro.kernels.possibility.ops import _prepare
    interpret = not poss_ops.backend_supports_pallas()
    topo = mesh2d(6, 5)
    t = traffic.uniform(topo)
    du, dn, dsn, tn, tm, dist = _prepare(topo.distances, t, topo.channels)
    v = possibility_v_pallas(du, dn, tm, dist, interpret=interpret)
    w_ref, wd_ref = possibility_oracle(topo.distances, t, topo.channels)
    np.testing.assert_allclose(np.asarray(v).sum(1), w_ref,
                               rtol=1e-5, atol=1e-7)
    ns = topo.channels[:, 1]
    np.testing.assert_allclose(
        np.asarray(v)[np.arange(topo.num_channels), ns], wd_ref,
        rtol=1e-5, atol=1e-7)


def test_possibility_v_pallas_multi_block_padded():
    """V entry by entry at 12×12 (N=144, C=528): two destination blocks
    and zero padding on the channel, source and destination axes."""
    from repro.kernels.possibility.kernel import possibility_v_pallas
    from repro.kernels.possibility.ops import _prepare
    interpret = not poss_ops.backend_supports_pallas()
    topo = mesh2d(12, 12)
    rng = np.random.default_rng(7)
    t = rng.random((topo.num_nodes,) * 2)
    np.fill_diagonal(t, 0.0)
    t /= t.sum()
    du, dn, dsn, tn, tm, dist = _prepare(topo.distances, t, topo.channels)
    v = np.asarray(possibility_v_pallas(du, dn, tm, dist,
                                        interpret=interpret))
    du, dn, tm, dist = (np.asarray(a) for a in (du, dn, tm, dist))
    v_ref = np.concatenate([
        np.einsum("csd,sd->cd",
                  (du.T[c0:c0 + 128, :, None] + 1 + dn[c0:c0 + 128, None]
                   == dist[None]).astype(np.float64), tm)
        for c0 in range(0, topo.num_channels, 128)])
    assert v.shape == v_ref.shape == (528, 144)
    np.testing.assert_allclose(v, v_ref, rtol=1e-5, atol=1e-9)
    w_ref, _ = possibility_oracle(topo.distances, t, topo.channels)
    np.testing.assert_allclose(v.sum(1), w_ref, rtol=1e-5, atol=1e-7)


@settings(max_examples=10, deadline=None)
@given(st.integers(3, 6), st.integers(3, 5), st.integers(0, 2**31 - 1))
def test_possibility_kernel_random_traffic(w, h, seed):
    topo = mesh2d(w, h)
    rng = np.random.default_rng(seed)
    t = rng.random((topo.num_nodes,) * 2)
    np.fill_diagonal(t, 0)
    t /= t.sum()
    w_ref, wd_ref = possibility_oracle(topo.distances, t, topo.channels)
    wk, wdk = poss_ops.possibility_weights(topo.distances, t, topo.channels)
    np.testing.assert_allclose(np.asarray(wk), w_ref, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(np.asarray(wdk), wd_ref, rtol=1e-4, atol=1e-6)


def test_possibility_kernel_block_sweep():
    interpret = not poss_ops.backend_supports_pallas()
    topo = torus(8, 8)
    t = traffic.uniform(topo)
    w_ref, _ = possibility_oracle(topo.distances, t, topo.channels)
    from repro.kernels.possibility.ops import _prepare
    from repro.kernels.possibility.kernel import possibility_weights_pallas
    args = _prepare(topo.distances, t, topo.channels)
    for bc, bs in [(32, 16), (64, 64), (256, 64), (128, 128)]:
        w, _ = possibility_weights_pallas(*args, block_c=bc, block_s=bs,
                                          interpret=interpret)
        np.testing.assert_allclose(np.asarray(w), w_ref, rtol=1e-5,
                                   atol=1e-7)


# --------------------------------------------------------------------- #
# flash attention
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("sq,skv,h,kv,d,causal,dtype", [
    (128, 128, 4, 4, 64, True, jnp.float32),
    (256, 256, 4, 2, 64, True, jnp.float32),
    (128, 256, 2, 1, 32, False, jnp.float32),
    (200, 200, 4, 2, 64, True, jnp.float32),     # non-multiple of block
    (128, 128, 4, 4, 64, True, jnp.bfloat16),
    (64, 512, 8, 2, 128, False, jnp.float32),
])
def test_flash_kernel_matches_ref(sq, skv, h, kv, d, causal, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    b = 2
    q = jax.random.normal(ks[0], (b, sq, h, d), dtype)
    k = jax.random.normal(ks[1], (b, skv, kv, d), dtype)
    v = jax.random.normal(ks[2], (b, skv, kv, d), dtype)
    out = flash_ops.flash_attention(q, k, v, causal=causal, block_q=64,
                                    block_kv=64)
    ref = flash_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                    v.transpose(0, 2, 1, 3),
                    causal=causal).transpose(0, 2, 1, 3)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@settings(max_examples=8, deadline=None)
@given(st.integers(1, 3), st.sampled_from([32, 64, 96]),
       st.sampled_from([1, 2, 4]), st.booleans(),
       st.integers(0, 2**31 - 1))
def test_flash_kernel_property(b, sq, g, causal, seed):
    kv, d = 2, 32
    h = kv * g
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, sq, h, d))
    k = jax.random.normal(ks[1], (b, sq, kv, d))
    v = jax.random.normal(ks[2], (b, sq, kv, d))
    out = flash_ops.flash_attention(q, k, v, causal=causal, block_q=32,
                                    block_kv=32)
    ref = flash_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                    v.transpose(0, 2, 1, 3),
                    causal=causal).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_kernel_output_is_convex_combination():
    """Attention outputs lie in the convex hull of V rows (max bound)."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 64, 2, 32))
    k = jax.random.normal(ks[1], (1, 64, 2, 32))
    v = jax.random.normal(ks[2], (1, 64, 2, 32))
    out = flash_ops.flash_attention(q, k, v, causal=False)
    assert float(jnp.abs(out).max()) <= float(jnp.abs(v).max()) + 1e-5


# --------------------------------------------------------------------- #
# mamba selective scan
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("b,s,di,ds,chunk", [
    (2, 64, 128, 16, 16),
    (1, 96, 256, 8, 32),     # s not a chunk multiple of block
    (2, 64, 100, 16, 64),    # di not a block multiple
    (1, 33, 64, 4, 16),
])
def test_mamba_scan_kernel_matches_ref(b, s, di, ds, chunk):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    delta = jax.nn.softplus(jax.random.normal(ks[0], (b, s, di))) * 0.1
    a = -jnp.exp(jax.random.normal(ks[1], (di, ds)) * 0.2)
    bm = jax.random.normal(ks[2], (b, s, ds))
    cm = jax.random.normal(ks[3], (b, s, ds))
    x = jax.random.normal(ks[4], (b, s, di))
    y = scan_ops.selective_scan(delta, a, bm, cm, x, block_d=64,
                                chunk=chunk)
    y_ref, _ = scan_ref(delta, a, bm, cm, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-5)


@settings(max_examples=6, deadline=None)
@given(st.integers(0, 2**31 - 1), st.sampled_from([16, 48, 64]))
def test_mamba_scan_property(seed, s):
    b, di, ds = 1, 64, 8
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    delta = jax.nn.softplus(jax.random.normal(ks[0], (b, s, di))) * 0.1
    a = -jnp.exp(jax.random.normal(ks[1], (di, ds)) * 0.2)
    bm = jax.random.normal(ks[2], (b, s, ds))
    cm = jax.random.normal(ks[3], (b, s, ds))
    x = jax.random.normal(ks[4], (b, s, di))
    y = scan_ops.selective_scan(delta, a, bm, cm, x, block_d=32, chunk=16)
    y_ref, _ = scan_ref(delta, a, bm, cm, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-5)


def test_mamba_scan_decays_to_zero_with_large_negative_a():
    """Stability: strongly negative A forgets history ⇒ y tracks only the
    instantaneous input."""
    b, s, di, ds = 1, 32, 32, 4
    delta = jnp.ones((b, s, di)) * 5.0
    a = -jnp.ones((di, ds)) * 10.0
    bm = jnp.ones((b, s, ds))
    cm = jnp.ones((b, s, ds))
    x = jnp.ones((b, s, di))
    y = scan_ops.selective_scan(delta, a, bm, cm, x, block_d=32, chunk=8)
    # steady state: h ≈ Δ·x·B (previous h fully decayed)
    np.testing.assert_allclose(np.asarray(y[0, -1]), 5.0 * ds,
                               rtol=1e-3)
