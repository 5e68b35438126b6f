"""Distributed linalg vs numpy golden solutions (mirrors the reference's
solver suites, e.g. BlockLinearMapperSuite / LeastSquaresEstimatorSuite)."""
import numpy as np
import pytest

from keystone_tpu.parallel.dataset import ArrayDataset
from keystone_tpu.ops import linalg


def make_problem(n=256, d=32, k=4, seed=0, dtype=np.float32):
    rng = np.random.RandomState(seed)
    A = rng.randn(n, d).astype(dtype)
    W = rng.randn(d, k).astype(dtype)
    Y = (A @ W + 0.01 * rng.randn(n, k)).astype(dtype)
    return A, Y, W


def ridge_numpy(A, Y, lam):
    d = A.shape[1]
    return np.linalg.solve(
        A.astype(np.float64).T @ A.astype(np.float64) + lam * np.eye(d),
        A.astype(np.float64).T @ Y.astype(np.float64),
    )


def test_gram_exact_with_padding():
    A, _, _ = make_problem(n=100)  # 100 not divisible by 8 -> padded
    ds = ArrayDataset.from_numpy(A)
    G = np.asarray(linalg.gram(ds.data))
    np.testing.assert_allclose(G, A.T @ A, rtol=1e-4)


def test_normal_equations_matches_numpy():
    A, Y, _ = make_problem()
    ds = ArrayDataset.from_numpy(A)
    ys = ArrayDataset.from_numpy(Y)
    W = np.asarray(linalg.normal_equations(ds.data, ys.data, lam=0.1))
    expect = ridge_numpy(A, Y, 0.1)
    np.testing.assert_allclose(W, expect, rtol=2e-3, atol=2e-3)


def test_local_least_squares_dual_matches_primal():
    # d >> n regime
    A, Y, _ = make_problem(n=32, d=128)
    W = np.asarray(linalg.local_least_squares_dual(A, Y, lam=0.5))
    expect = ridge_numpy(A, Y, 0.5)
    np.testing.assert_allclose(W, expect, rtol=5e-3, atol=5e-3)


def test_bcd_single_block_equals_normal_equations():
    A, Y, _ = make_problem()
    ds = ArrayDataset.from_numpy(A)
    ys = ArrayDataset.from_numpy(Y)
    Ws = linalg.block_coordinate_descent([ds.data], ys.data, lam=0.1, num_passes=1)
    expect = ridge_numpy(A, Y, 0.1)
    np.testing.assert_allclose(np.asarray(Ws[0]), expect, rtol=2e-3, atol=2e-3)


def test_bcd_converges_to_full_solve():
    """Multi-pass BCD over blocks approaches the joint ridge solution
    (reference BlockLinearMapperSuite: block solver vs single-matrix)."""
    A, Y, _ = make_problem(n=512, d=48, k=3, seed=1)
    lam = 0.5
    blocks_np = [A[:, :16], A[:, 16:32], A[:, 32:]]
    blocks = [ArrayDataset.from_numpy(b).data for b in blocks_np]
    ys = ArrayDataset.from_numpy(Y)
    Ws = linalg.block_coordinate_descent(blocks, ys.data, lam=lam, num_passes=30)
    W = np.concatenate([np.asarray(w) for w in Ws], axis=0)
    expect = ridge_numpy(A, Y, lam)
    np.testing.assert_allclose(W, expect, rtol=2e-2, atol=2e-2)


def test_bcd_one_pass_reduces_objective():
    A, Y, _ = make_problem(n=512, d=48, k=3, seed=2)
    blocks_np = [A[:, :24], A[:, 24:]]
    blocks = [ArrayDataset.from_numpy(b).data for b in blocks_np]
    ys = ArrayDataset.from_numpy(Y)
    Ws = linalg.solve_one_pass_l2(blocks, ys.data, lam=0.1)
    W = np.concatenate([np.asarray(w) for w in Ws], axis=0)
    resid = np.linalg.norm(A @ W - Y)
    assert resid < 0.5 * np.linalg.norm(Y)


def test_tsqr_r_matches_numpy():
    A, _, _ = make_problem(n=512, d=16)
    ds = ArrayDataset.from_numpy(A)
    R = np.asarray(linalg.tsqr_r(ds.data))
    # Compare via A^T A = R^T R and sign-fixed R against numpy
    np.testing.assert_allclose(R.T @ R, A.T @ A, rtol=1e-3, atol=1e-3)
    Rnp = np.linalg.qr(A, mode="r")
    Rnp = Rnp * np.sign(np.diag(Rnp))[:, None]
    np.testing.assert_allclose(np.abs(R), np.abs(Rnp), rtol=2e-3, atol=2e-3)
    assert np.all(np.diag(R) >= 0)


def test_tsqr_short_shards_pad_and_stay_distributed():
    # 10 rows over 8 shards would leave shards shorter than d=6; the
    # pad-and-mask path zero-pads to 6 rows/shard and stays exact.
    A = np.random.RandomState(0).randn(10, 6).astype(np.float32)
    R = np.asarray(linalg.tsqr_r(ArrayDataset.from_numpy(A).data))
    assert R.shape == (6, 6)
    np.testing.assert_allclose(R.T @ R, A.T @ A, rtol=1e-3, atol=1e-3)


def test_tsqr_uneven_rows_match_numpy():
    # n not divisible by the shard count: the zero-pad branch inside
    # tsqr_r must fire (raw array, not ArrayDataset, which would
    # pre-pad) and agree with a plain host QR up to the sign convention.
    import jax.numpy as jnp

    A = np.random.RandomState(1).randn(173, 12).astype(np.float32)
    R = np.asarray(linalg.tsqr_r(jnp.asarray(A)))
    assert R.shape == (12, 12)
    Rnp = np.linalg.qr(A, mode="r")
    Rnp = Rnp * np.sign(np.diag(Rnp))[:, None]
    np.testing.assert_allclose(R, Rnp, rtol=2e-3, atol=2e-3)
    assert np.all(np.diag(R) >= 0)


def test_tsqr_wide_matrix_replicated_fallback():
    # n < d is not tall-skinny; R is (n, d) from the replicated path.
    A = np.random.RandomState(2).randn(5, 9).astype(np.float32)
    padded = np.asarray(ArrayDataset.from_numpy(A).data)  # rows padded to shards
    R = np.asarray(linalg.tsqr_r(ArrayDataset.from_numpy(A).data))
    assert R.shape == (padded.shape[0], 9) and R.shape[0] < 9
    np.testing.assert_allclose(R.T @ R, A.T @ A, rtol=1e-3, atol=1e-3)


def test_distributed_mean_with_padding():
    A, _, _ = make_problem(n=100, d=8)
    ds = ArrayDataset.from_numpy(A)
    m = np.asarray(linalg.distributed_mean(ds.data, ds.n))
    np.testing.assert_allclose(m, A.mean(axis=0), rtol=1e-4, atol=1e-5)


def test_bcd_class_columns_shard_over_model_axis():
    """VERDICT r1 next#4 for the PLAIN solver: with a ('data','model')
    mesh, bcd_core shards label columns over 'model' (cross-products,
    cho_solve RHS, prediction updates split by class group) and matches
    the single-axis result exactly."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from keystone_tpu.parallel.mesh import (
        DATA_AXIS, MODEL_AXIS, make_mesh, mesh_scope,
    )

    devs = jax.devices()[:8]
    A, Y, _ = make_problem(n=160, d=24, k=8, seed=7)
    lam = 0.3

    with mesh_scope(make_mesh(devs, data=8, model=1)):
        W1 = linalg.block_coordinate_descent(
            [jax.numpy.asarray(A[:, :12]), jax.numpy.asarray(A[:, 12:])],
            jax.numpy.asarray(Y), lam, num_passes=3)
        W1 = np.concatenate([np.asarray(w) for w in W1])

    mesh = make_mesh(devs, data=4, model=2)
    with mesh_scope(mesh):
        Aj = jax.device_put(A, NamedSharding(mesh, P(DATA_AXIS, None)))
        Yj = jax.device_put(Y, NamedSharding(mesh, P(DATA_AXIS, None)))
        Ws = linalg.block_coordinate_descent(
            [Aj[:, :12], Aj[:, 12:]], Yj, lam, num_passes=3)
        # returned block weights are sharded over 'model' (k split 2-ways)
        shard_shapes = {s.data.shape for s in Ws[0].addressable_shards}
        assert shard_shapes == {(12, 4)}
        W2 = np.concatenate([np.asarray(w) for w in Ws])

    np.testing.assert_allclose(W1, W2, rtol=2e-4, atol=2e-4)
    # both solutions agree with the full normal-equations solve
    ref = ridge_numpy(A, Y, lam)
    for W in (W1, W2):
        assert np.linalg.norm(W - ref) / np.linalg.norm(ref) < 0.05


def test_gram_symmetric_tiled_path_matches_full():
    # d >= _GRAM_SYM_MIN_D with an admissible tile takes the
    # upper-triangle syrk assembly; must equal the fused einsum exactly
    # in structure and to f32 tolerance in value, and be symmetric
    rng = np.random.RandomState(7)
    A = rng.randn(96, 2048).astype(np.float32)
    import jax.numpy as jnp
    G = np.asarray(linalg.gram(jnp.asarray(A)))
    ref = A.T @ A
    assert G.shape == (2048, 2048)
    assert np.array_equal(G, G.T)
    assert np.allclose(G, ref, rtol=2e-5, atol=2e-4)


def test_gram_sym_tile_selection():
    # cap on the unrolled tile grid: tile widens for very wide A, and
    # non-divisible widths fall back (None) to the fused einsum
    from keystone_tpu.ops.linalg import _gram_sym_tile

    assert _gram_sym_tile(4096) == 512       # 8 tiles
    assert _gram_sym_tile(8192) == 512       # 16 tiles (at the cap)
    assert _gram_sym_tile(16384) == 1024     # cap doubles the tile
    assert _gram_sym_tile(2304) is None      # 512 does not divide


def test_near_breakdown_finite_factor_takes_eigh_fallback():
    # A near-duplicate column makes the Gram near-exactly-singular: f32
    # Cholesky returns a FINITE factor whose last pivot collapsed to
    # rounding noise (the "tiny positive pivot instead of a negative
    # one" regime ADVICE r2 flagged), and the raw solve produces wild
    # ~1e5-norm weights. The conditioning gate must route the solve to
    # the eigh-clamped recovery instead.
    import jax.numpy as jnp

    rng = np.random.RandomState(3)
    n, d, k = 256, 64, 3
    A = rng.randn(n, d).astype(np.float32)
    A[:, -1] = A[:, 0] + 1e-5 * rng.randn(n).astype(np.float32)
    G = (A.T @ A).astype(np.float32)
    rhs = rng.randn(d, k).astype(np.float32)

    W = np.asarray(linalg.ridge_cho_solve(
        jnp.asarray(G), jnp.asarray(rhs), 0.0))
    assert np.isfinite(W).all()

    V, wc = linalg.clamped_eigh(jnp.asarray(G))
    expected = np.asarray((V * (1.0 / wc)) @ (V.T @ jnp.asarray(rhs)))
    assert np.allclose(W, expected, rtol=1e-3, atol=1e-3), (
        np.abs(W - expected).max())
    # and the recovery is the point: bounded weights, not the raw
    # solve's ~1e5-norm blowup
    assert np.linalg.norm(W) < 1e3, np.linalg.norm(W)


def test_healthy_conditioning_keeps_cholesky_path():
    # kappa ~ 1e4 (well inside reference conditioning) must NOT take the
    # more-strongly-regularized fallback: the solve stays the accurate
    # Cholesky result, far from the clamped-eigh answer.
    import jax.numpy as jnp

    rng = np.random.RandomState(4)
    d, k = 64, 3
    Q = np.linalg.qr(rng.randn(d, d))[0]
    eig = np.logspace(0, -4, d)
    G = ((Q * eig) @ Q.T).astype(np.float32)
    rhs = rng.randn(d, k).astype(np.float32)

    W = np.asarray(linalg.ridge_cho_solve(
        jnp.asarray(G), jnp.asarray(rhs), 0.0))
    W64 = np.linalg.solve(G.astype(np.float64), rhs.astype(np.float64))
    assert np.abs(W - W64).max() / np.abs(W64).max() < 1e-2


def test_badly_scaled_well_conditioned_keeps_cholesky_path():
    # G = D C D with C well-conditioned and diagonal scales spanning
    # 1e4: raw-kappa looks ~1e8 but the f32 Cholesky solve is accurate
    # to ~1e-7 — the scale-free pivot gate must NOT misroute it to the
    # much-more-regularized eigh fallback (review r3 finding).
    import jax.numpy as jnp

    rng = np.random.RandomState(5)
    n, d, k = 256, 64, 3
    B = rng.randn(n, d)
    C = B.T @ B / n
    D = np.logspace(0, -4, d)
    G = ((C * D[None, :]) * D[:, None]).astype(np.float32)
    rhs = (rng.randn(d, k) * D[:, None]).astype(np.float32)

    W = np.asarray(linalg.ridge_cho_solve(
        jnp.asarray(G), jnp.asarray(rhs), 0.0))
    W64 = np.linalg.solve(G.astype(np.float64), rhs.astype(np.float64))
    rel = np.abs(W - W64).max() / np.abs(W64).max()
    assert rel < 1e-3, rel


def test_bcd_scan_matches_unrolled():
    # 4+ equal-width blocks route through bcd_core's lax.scan body (the
    # dispatch itself is exercised here, not just the body); the scan
    # result must be numerically identical (same sequential update
    # order) to the unrolled path, which ragged/small lists still use
    import jax.numpy as jnp

    rng = np.random.RandomState(11)
    n, k = 192, 3
    X = rng.randn(n, 128).astype(np.float32)
    Y = rng.randn(n, k).astype(np.float32)
    blocks = tuple(jnp.asarray(X[:, i:i + 32]) for i in range(0, 128, 32))
    lam = jnp.float32(0.05)
    # through the public dispatch: 4 equal blocks -> scan body
    via_core = linalg.bcd_core(blocks, jnp.asarray(Y), lam, num_passes=3)
    # direct bodies for the equivalence claim
    scan_out = linalg._bcd_scan_body(blocks, jnp.asarray(Y), lam,
                                     num_passes=3)
    unrolled = linalg._bcd_core_body(blocks, jnp.asarray(Y), lam,
                                     num_passes=3)
    for a, b, c in zip(via_core, scan_out, unrolled):
        assert np.allclose(np.asarray(a), np.asarray(b), rtol=0, atol=0), \
            "bcd_core must dispatch 4 equal blocks to the scan body"
        assert np.allclose(np.asarray(b), np.asarray(c),
                           rtol=1e-5, atol=1e-5)
    # ragged lists stay on the unrolled path (scan would crash on
    # stack); values must match a direct unrolled-body call
    ragged = (jnp.asarray(X[:, :48]), jnp.asarray(X[:, 48:96]),
              jnp.asarray(X[:, 96:]), jnp.asarray(X[:, 96:]))
    out = linalg.bcd_core(ragged, jnp.asarray(Y), lam, num_passes=1)
    ref = linalg._bcd_core_body(ragged, jnp.asarray(Y), lam, num_passes=1)
    assert len(out) == 4
    for a, b in zip(out, ref):
        assert np.allclose(np.asarray(a), np.asarray(b),
                           rtol=1e-5, atol=1e-5)


# -- the sweep over a block maker (PR 29) -------------------------------------

def sweep_problem(pad, singular, seed, n=200, bs=16, B=5, k=3):
    """Rows, labels and mask of a solve on ``B`` equal blocks: ``pad``
    zero rows after the true ones, and with ``singular`` a duplicated
    column in block 1, which breaks its lambda = 0 factor so that the
    recovery branch runs."""
    rng = np.random.RandomState(seed)
    X = rng.randn(n + pad, bs * B).astype(np.float32)
    Y = rng.randn(n + pad, k).astype(np.float32)
    if singular:
        X[:, bs + 3] = X[:, bs]
    X[n:] = 0
    Y[n:] = 0
    bounds = tuple((i, i + bs) for i in range(0, bs * B, bs))
    return X, Y, np.arange(n + pad) < n, n, bounds


def centred_blocks(X, Y, mask, n, bounds):
    """What ``_block_solve`` hands the solver, as a list: every block
    and the labels centred on the true rows' means, padded rows zero."""
    import jax.numpy as jnp

    X, Y = jnp.asarray(X), jnp.asarray(Y)
    m = jnp.asarray(mask)[:, None].astype(X.dtype)
    x_mean = linalg.distributed_mean(X, n)
    y_mean = linalg.distributed_mean(Y, n)
    return [(X[:, lo:hi] - x_mean[lo:hi]) * m for lo, hi in bounds], \
        (Y - y_mean) * m


SWEEP_CASES = [(p, pad, lam, False) for p in (1, 2, 3) for pad in (0, 5)
               for lam in (0.0, 0.05)] + [(1, 0, 0.0, True), (2, 5, 0.0, True)]


@pytest.mark.parametrize("passes,pad,lam,singular", SWEEP_CASES)
def test_listed_sweep_equals_unrolled_bit_for_bit(passes, pad, lam, singular):
    """``bcd_core`` on 5 equal blocks takes the sweep with the
    ``lax.switch`` maker; its factor sweep is its first pass, and it
    returns the unrolled body's numbers bit for bit."""
    import jax
    import jax.numpy as jnp

    blocks, Yc = centred_blocks(*sweep_problem(pad, singular, 10 * passes + pad))
    lam = jnp.float32(lam)
    if singular:  # the case is what it says: block 1's factor is refused
        G = linalg.gram(blocks[1])
        assert not linalg._chol_healthy(
            jax.scipy.linalg.cho_factor(G, lower=True)[0], G)
    got = jax.jit(lambda b, y: linalg.bcd_core(b, y, lam, num_passes=passes))(
        blocks, Yc)
    want = jax.jit(lambda b, y: linalg._bcd_core_body(
        b, y, lam, num_passes=passes))(blocks, Yc)
    assert len(got) == len(want) == 5
    for a, b in zip(got, want):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0, atol=0)


def bcd_form_counters():
    from keystone_tpu.observability.metrics import MetricsRegistry

    registry = MetricsRegistry.get_or_create()
    return {form: registry.counter(f"solve.bcd.{form}").value
            for form in ("sliced", "listed", "unrolled")}


@pytest.mark.parametrize("widths,form", [
    ((8, 8, 8, 8), "listed"), ((8, 8, 8), "unrolled"),
    ((8, 8, 8, 12), "unrolled")])
def test_bcd_core_counts_the_form_when_it_is_traced(widths, form):
    """The shapes choose the form, so ``solve.bcd.<form>`` rises once
    when ``bcd_core`` is traced and not again when the program runs."""
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(len(widths))
    blocks = [jnp.asarray(rng.randn(40, w).astype(np.float32)) for w in widths]
    Y = jnp.asarray(rng.randn(40, 2).astype(np.float32))
    solve = jax.jit(lambda b, y: linalg.bcd_core(
        b, y, jnp.float32(0.1), num_passes=2))
    before = bcd_form_counters()
    solve(blocks, Y)
    solve(blocks, Y)
    after = bcd_form_counters()
    assert {f: after[f] - before[f] for f in after} == {
        f: float(f == form) for f in after}


# -- recovery of a block too wide for an eigh's program (PR 33) -----------------

@pytest.mark.parametrize("passes,singular", [(1, False), (1, True), (2, True)])
def test_a_block_wider_than_the_eigh_limit_recovers_by_the_raised_diagonal(
        passes, singular, monkeypatch):
    """Over ``EIGH_RECOVERY_MAX_COLUMNS`` the sweep recovers a broken
    factor as the streamed form does (the floor ``clamped_eigh`` would
    clamp to, added to the diagonal, and a second Cholesky): no ``eigh``
    in the program, the same numbers bit for bit while every factor is
    healthy, and on a singular block weights that fit as the eigh's do."""
    import jax
    import jax.numpy as jnp

    blocks, Yc = centred_blocks(*sweep_problem(0, singular, 7))
    lam = jnp.float32(0.0)

    def solve(b, y):
        return linalg.bcd_core(b, y, lam, num_passes=passes)

    want = jax.jit(solve)(blocks, Yc)                     # blocks of 16 <= 2,048
    assert "eigh" in jax.jit(solve).lower(blocks, Yc).as_text().lower()
    monkeypatch.setattr(linalg, "EIGH_RECOVERY_MAX_COLUMNS", 8)
    narrow = jax.jit(lambda b, y: solve(b, y))
    got = narrow(blocks, Yc)
    assert "eigh" not in narrow.lower(blocks, Yc).as_text().lower()
    for a, b in zip(got, want):
        assert np.isfinite(np.asarray(a)).all()
        if not singular:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if singular:
        def residual(Ws):
            pred = sum(A @ W for A, W in zip(blocks, Ws))
            return float(jnp.linalg.norm(Yc - pred) / jnp.linalg.norm(Yc))
        assert abs(residual(got) - residual(want)) < 1e-3
        assert float(jnp.max(jnp.abs(got[1]))) < 10 * float(
            jnp.max(jnp.abs(want[1])))
