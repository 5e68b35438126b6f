"""Linear model node tests (mirrors BlockLinearMapperSuite /
LinearMapperSuite)."""
import numpy as np
import pytest

from keystone_tpu.nodes.learning import (
    BlockLeastSquaresEstimator,
    BlockLinearMapper,
    LinearMapEstimator,
    LinearMapper,
)
from keystone_tpu.parallel.dataset import ArrayDataset


def make_problem(n=200, d=24, k=3, seed=0):
    rng = np.random.RandomState(seed)
    A = rng.randn(n, d).astype(np.float32)
    W = rng.randn(d, k).astype(np.float32)
    b = rng.randn(k).astype(np.float32)
    Y = (A @ W + b + 0.01 * rng.randn(n, k)).astype(np.float32)
    return A, Y


def centered_ridge(A, Y, lam):
    Am, Ym = A.mean(0), Y.mean(0)
    Ac = (A - Am).astype(np.float64)
    Yc = (Y - Ym).astype(np.float64)
    W = np.linalg.solve(Ac.T @ Ac + lam * np.eye(A.shape[1]), Ac.T @ Yc)
    return W, Am, Ym


def test_linear_map_estimator_matches_centered_ridge():
    A, Y = make_problem()
    model = LinearMapEstimator(lam=0.5).fit(A, Y)
    W, Am, Ym = centered_ridge(A, Y, 0.5)
    np.testing.assert_allclose(model.weights, W, rtol=2e-3, atol=2e-3)
    out = model(A).numpy()
    expect = (A - Am) @ W + Ym
    np.testing.assert_allclose(out, expect, rtol=2e-3, atol=2e-3)


def test_block_least_squares_single_block_matches_ridge():
    A, Y = make_problem()
    model = BlockLeastSquaresEstimator(block_size=64, num_iter=1, lam=0.3).fit(A, Y)
    W, Am, Ym = centered_ridge(A, Y, 0.3)
    np.testing.assert_allclose(model.weights, W, rtol=5e-3, atol=5e-3)


def test_block_least_squares_multi_block_converges():
    """Block solver approaches the exact joint solve with iterations
    (reference BlockLinearMapperSuite:17-55)."""
    A, Y = make_problem(n=400, d=30, k=2, seed=3)
    lam = 0.4
    model = BlockLeastSquaresEstimator(block_size=10, num_iter=25, lam=lam).fit(A, Y)
    W, Am, Ym = centered_ridge(A, Y, lam)
    np.testing.assert_allclose(model.weights, W, rtol=3e-2, atol=3e-2)
    out = model(A).numpy()
    expect = (A - Am) @ W + Ym
    np.testing.assert_allclose(out, expect, rtol=5e-2, atol=5e-2)


def test_block_linear_mapper_apply_blocks_equivalent():
    rng = np.random.RandomState(0)
    blocks = [rng.randn(8, 3).astype(np.float32) for _ in range(3)]
    x = rng.randn(5, 24).astype(np.float32)
    mapper = BlockLinearMapper(blocks, 8)
    out = mapper(x).numpy()
    expect = x @ np.concatenate(blocks, 0)
    np.testing.assert_allclose(out, expect, rtol=1e-4, atol=1e-4)


def test_weight_property():
    est = BlockLeastSquaresEstimator(block_size=10, num_iter=4, lam=0)
    assert est.weight == 13  # 3*numIter+1, BlockLinearMapper.scala:204


def test_padding_does_not_corrupt_solve():
    # n=101 deliberately not divisible by 8
    A, Y = make_problem(n=101, d=16, k=2, seed=5)
    model = BlockLeastSquaresEstimator(block_size=16, num_iter=1, lam=0.2).fit(A, Y)
    W, Am, Ym = centered_ridge(A, Y, 0.2)
    np.testing.assert_allclose(model.weights, W, rtol=5e-3, atol=5e-3)


def test_linear_compute_cost_matches_numpy():
    """LinearMapEstimator.computeCost (reference LinearMapper.scala:124-161):
    objective = ||AW + b - Y||^2/(2n) + lam/2 ||W||^2."""
    A, Y = make_problem(n=120, d=10, k=3, seed=5)
    rng = np.random.RandomState(6)
    W = rng.randn(10, 3).astype(np.float32)
    b = rng.randn(3).astype(np.float32)
    lam = 0.7
    got = LinearMapEstimator.compute_cost(A, Y, lam, W, b)
    want = (np.linalg.norm(A @ W + b - Y) ** 2) / (2 * A.shape[0]) + (
        lam / 2
    ) * np.sum(W**2)
    np.testing.assert_allclose(got, want, rtol=1e-4)
    # lam=0 branch and no intercept
    got0 = LinearMapEstimator.compute_cost(A, Y, 0.0, W, None)
    want0 = (np.linalg.norm(A @ W - Y) ** 2) / (2 * A.shape[0])
    np.testing.assert_allclose(got0, want0, rtol=1e-4)


def test_block_compute_cost_matches_numpy():
    """BlockLeastSquaresEstimator.computeCost (BlockLinearMapper.scala:144-187)."""
    A, Y = make_problem(n=100, d=12, k=2, seed=7)
    rng = np.random.RandomState(8)
    bounds = [(0, 5), (5, 10), (10, 12)]
    Ws = [rng.randn(hi - lo, 2).astype(np.float32) for lo, hi in bounds]
    b = rng.randn(2).astype(np.float32)
    lam = 0.3
    blocks = [A[:, lo:hi] for lo, hi in bounds]
    got = BlockLeastSquaresEstimator.compute_cost(blocks, Y, lam, Ws, b)
    pred = sum(blk @ w for blk, w in zip(blocks, Ws)) + b
    want = (np.linalg.norm(pred - Y) ** 2) / (2 * A.shape[0]) + (lam / 2) * sum(
        np.sum(w**2) for w in Ws
    )
    np.testing.assert_allclose(got, want, rtol=1e-4)


def test_apply_and_evaluate_incremental(mesh8):
    """BlockLinearMapper.applyAndEvaluate (BlockLinearMapper.scala:105-142):
    evaluator sees the cumulative per-block predictions; the last call
    equals full apply()."""
    A, Y = make_problem(n=96, d=12, k=3, seed=9)
    mapper = BlockLeastSquaresEstimator(block_size=4, num_iter=4, lam=0.1).fit(
        A, Y
    )
    bounds = mapper._block_bounds()
    blocks = [A[:, lo:hi] for lo, hi in bounds]

    seen = []
    mapper.apply_and_evaluate(blocks, lambda ds: seen.append(ds.numpy()))
    assert len(seen) == len(mapper.block_weights)

    # incremental partials match the cumulative numpy sums (+ intercept)
    partial = np.zeros((A.shape[0], 3), np.float64)
    for i, ((lo, hi), w) in enumerate(zip(bounds, mapper.block_weights)):
        x = blocks[i]
        if mapper.feature_means is not None:
            x = x - mapper.feature_means[lo:hi]
        partial = partial + x.astype(np.float64) @ np.asarray(w, np.float64)
        want = partial + (0 if mapper.intercept is None else mapper.intercept)
        np.testing.assert_allclose(seen[i], want, rtol=2e-3, atol=2e-3)

    # final evaluation == full apply
    np.testing.assert_allclose(seen[-1], mapper(A).numpy(), rtol=2e-3, atol=2e-3)


def test_apply_and_evaluate_pad_rows_stay_zero(mesh8):
    """Pad rows of the emitted datasets must honor ArrayDataset's zero-pad
    invariant even though centering/intercept would otherwise fill them."""
    from keystone_tpu.parallel.dataset import ArrayDataset

    A, Y = make_problem(n=101, d=8, k=2, seed=11)  # 101 % 8 != 0 -> padding
    mapper = BlockLeastSquaresEstimator(block_size=4, num_iter=2, lam=0.1).fit(
        A, Y
    )
    blocks = [
        ArrayDataset.from_numpy(A[:, lo:hi]) for lo, hi in mapper._block_bounds()
    ]
    outs = []
    mapper.apply_and_evaluate(blocks, lambda ds: outs.append(ds))
    for ds in outs:
        data = np.asarray(ds.data)
        assert data.shape[0] > ds.n  # padding actually present
        np.testing.assert_array_equal(data[ds.n:], 0.0)


def test_block_least_squares_staged_core_matches_estimator(mesh8):
    """The public staged core (block_least_squares, which a caller may
    stage into a larger jit) must produce exactly the model the
    estimator's _fit path returns, including means and intercept."""
    import jax.numpy as jnp

    from keystone_tpu.nodes.learning.linear import block_least_squares

    A, Y = make_problem(n=160, d=24, k=3, seed=5)
    bounds = tuple((i, min(24, i + 8)) for i in range(0, 24, 8))

    model = BlockLeastSquaresEstimator(block_size=8, num_iter=2, lam=0.3).fit(
        A, Y)
    Ws, x_mean, y_mean = block_least_squares(
        jnp.asarray(A), jnp.asarray(Y), 160, 0.3, bounds, 2)

    np.testing.assert_allclose(
        np.asarray(model.weights),
        np.concatenate([np.asarray(w) for w in Ws], axis=0),
        rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(model.feature_means), np.asarray(x_mean),
        rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(model.intercept), np.asarray(y_mean),
        rtol=1e-5, atol=1e-5)
    # prediction identity: (x - x_mean) @ W + y_mean == model.apply(x)
    pred = (A - np.asarray(x_mean)) @ np.concatenate(
        [np.asarray(w) for w in Ws], axis=0) + np.asarray(y_mean)
    np.testing.assert_allclose(
        np.asarray(model(A).numpy()), pred, rtol=1e-4, atol=1e-4)


def test_fitted_mapper_eq_key_is_device_cheap():
    """eq_key must not serialize the full weight matrix (that is a full
    d2h of a fitted model during fusion/CSE); equal models compare
    equal, different models differ."""
    A, Y = make_problem(seed=7)
    m1 = LinearMapEstimator(lam=0.5).fit(A, Y)
    m2 = LinearMapEstimator(lam=0.5).fit(A, Y)
    m3 = LinearMapEstimator(lam=5.0).fit(A, Y)
    assert m1.eq_key() == m2.eq_key()
    assert m1.eq_key() != m3.eq_key()

    # the key may carry small host vectors (scaler means) but never the
    # weight-matrix payload
    def payload(t):
        for x in t:
            if isinstance(x, tuple):
                yield from payload(x)
            elif isinstance(x, bytes):
                yield len(x)
            elif isinstance(x, np.ndarray):
                yield x.nbytes
    assert sum(payload(m1.eq_key())) < m1.weights.size * 4

    b1 = BlockLeastSquaresEstimator(block_size=8, num_iter=1, lam=0.2).fit(A, Y)
    b2 = BlockLeastSquaresEstimator(block_size=8, num_iter=1, lam=0.2).fit(A, Y)
    assert b1.eq_key() == b2.eq_key()
    assert sum(payload(b1.eq_key())) < np.asarray(b1.weights).size * 4


def test_nan_weights_token_is_cache_stable(caplog):
    """A fitted model with non-finite weights must still equal an
    identically-valued copy (NaN != NaN would make models unequal to
    themselves, silently defeating CSE/fusion/jit caches), and the
    non-finite solve must be loudly flagged."""
    import logging

    from keystone_tpu.nodes.learning.linear import BlockLinearMapper

    W = np.full((4, 3), np.nan, np.float32)
    with caplog.at_level(logging.WARNING):
        a = BlockLinearMapper([W], 4)
        b = BlockLinearMapper([W.copy()], 4)
        assert a.eq_key() == b.eq_key()
        assert hash(a) == hash(b)
    assert any("non-finite" in r.message for r in caplog.records)


def test_nan_token_distinguishes_different_broken_models():
    """Two NaN-containing models with different finite content must NOT
    collapse to one eq_key (a cache substituting one broken model for
    another would serve wrong predictions with no error)."""
    from keystone_tpu.nodes.learning.linear import BlockLinearMapper

    Wa = np.arange(12, dtype=np.float32).reshape(4, 3)
    Wb = Wa * 2.0
    Wa[0, 0] = np.nan
    Wb[0, 0] = np.nan
    a = BlockLinearMapper([Wa], 4)
    b = BlockLinearMapper([Wb], 4)
    assert a.eq_key() != b.eq_key()


def test_cholesky_breakdown_recovers_finite_solution(mesh8):
    """kappa >> 1/eps_f32 with tiny lambda NaNs the f32 Cholesky; the
    eigh-clamped fallback must recover finite weights whose predictions
    beat chance (the reference's f64 solver survived this regime; a
    silent all-NaN model predicts one constant class)."""
    from keystone_tpu.nodes.learning import BlockLeastSquaresEstimator
    from keystone_tpu.nodes.util import ClassLabelIndicatorsFromIntLabels
    from keystone_tpu.parallel.dataset import ArrayDataset

    rng = np.random.RandomState(0)
    n, d, k = 128, 512, 10
    # huge-scale rank-deficient features: Gram kappa ~ 1e10 at lam 1e-2
    y = rng.randint(0, k, n)
    protos = rng.randn(k, d).astype(np.float32) * 300.0
    X = (protos[y] + 30.0 * rng.randn(n, d)).astype(np.float32)
    ds = ArrayDataset.from_numpy(X)
    labels = ClassLabelIndicatorsFromIntLabels(k)(
        ArrayDataset.from_numpy(y.astype(np.int32)))
    # prove this fixture genuinely breaks the plain f32 Cholesky (so a
    # pass below means the fallback produced the weights)
    from keystone_tpu.ops import linalg as L
    import jax.numpy as jnp
    import jax.scipy.linalg as jsl

    Xc = X - X.mean(0)
    G = jnp.asarray(np.asarray(L.gram(jnp.asarray(Xc)))
                    + 1e-2 * np.eye(d, dtype=np.float32))
    plain = np.asarray(jsl.cho_solve(
        jsl.cho_factor(G, lower=True),
        jnp.ones((d, k), jnp.float32)))
    assert not np.all(np.isfinite(plain)), "fixture no longer breaks down"

    model = BlockLeastSquaresEstimator(d, 1, 1e-2).fit(ds, labels)
    W = np.asarray(model.weights)
    assert np.all(np.isfinite(W))
    preds = np.asarray(model.apply_dataset(ds).numpy()).argmax(axis=1)
    assert (preds == y).mean() > 0.5  # far above the 0.1 chance floor


def test_finite_or_eigh_fallback_fires_directly():
    """Direct unit pin of the fallback branch: a NaN primary result must
    yield the eigh-clamped solution, and a finite one must pass through
    untouched."""
    import jax.numpy as jnp

    from keystone_tpu.ops.linalg import _finite_or_eigh_solve

    rng = np.random.RandomState(0)
    d, k = 16, 3
    M = rng.randn(d, d).astype(np.float32)
    reg = M @ M.T + 0.5 * np.eye(d, dtype=np.float32)  # well-conditioned
    rhs = rng.randn(d, k).astype(np.float32)
    expect = np.linalg.solve(reg, rhs)

    bad = jnp.full((d, k), np.nan, jnp.float32)
    out = np.asarray(_finite_or_eigh_solve(
        bad, lambda: jnp.asarray(reg), jnp.asarray(rhs)))
    np.testing.assert_allclose(out, expect, rtol=1e-3, atol=1e-3)

    good = jnp.asarray(expect + 1.0)  # any finite array passes through
    out2 = np.asarray(_finite_or_eigh_solve(
        good, lambda: jnp.asarray(reg), jnp.asarray(rhs)))
    np.testing.assert_array_equal(out2, np.asarray(good))


def test_block_least_squares_mesh_switch():
    """Regression (round 6's weighted-solver phase failure on 8 devices):
    ``_block_solve`` was one module-lifetime jit, and ``bcd_core``
    reads the ambient mesh through ``_class_spec`` — so the first
    mesh's class-sharding constraints baked into the cached trace and
    replayed against a second mesh's arguments at the same shapes
    ("incompatible devices: argument ... device ids [0] ...
    sharding_constraint ... [0..7]"). The per-mesh
    ``_block_solve_for`` factory keys the trace cache by mesh: an
    8-device ('data' x 'model') fit followed by a 1-device fit at
    IDENTICAL shapes must both run, and agree to f32 rounding (the
    dryrun_multichip parity bar)."""
    import jax

    from keystone_tpu.parallel.mesh import make_mesh, mesh_scope

    A, Y = make_problem(n=64, d=16, k=2, seed=1)
    est = BlockLeastSquaresEstimator(block_size=8, num_iter=2, lam=0.2)
    devices = jax.devices()[:8]
    with mesh_scope(make_mesh(devices, data=4, model=2)):
        w_n = np.asarray(est.fit(A, Y).weights)
    with mesh_scope(make_mesh(devices[:1], data=1, model=1)):
        w_1 = np.asarray(est.fit(A, Y).weights)
    scale = max(float(np.max(np.abs(w_1))), 1e-6)
    assert float(np.max(np.abs(w_n - w_1))) / scale < 5e-3


# -- the block solve on the design matrix in place (PR 29) --------------------

from test_linalg import (  # noqa: E402
    SWEEP_CASES, bcd_form_counters, centred_blocks, sweep_problem)


@pytest.mark.parametrize("passes,pad,lam,singular", SWEEP_CASES)
def test_sliced_sweep_equals_unrolled_bit_for_bit(passes, pad, lam, singular):
    """``block_least_squares`` on 5 equal blocks cuts each block out of
    the design matrix and centres it when the sweep reaches it; the
    weights are the unrolled body's on the list of centred blocks, bit
    for bit."""
    import jax
    import jax.numpy as jnp

    from keystone_tpu.nodes.learning.linear import block_least_squares
    from keystone_tpu.ops import linalg

    X, Y, mask, n, bounds = sweep_problem(pad, singular, 10 * passes + pad)
    got, _, _ = block_least_squares(
        jnp.asarray(X), jnp.asarray(Y), n, lam, bounds, passes,
        mask=jnp.asarray(mask))
    blocks, Yc = centred_blocks(X, Y, mask, n, bounds)
    want = jax.jit(lambda b, y: linalg._bcd_core_body(
        b, y, jnp.float32(lam), num_passes=passes))(blocks, Yc)
    assert len(got) == len(want) == len(bounds)
    for a, b in zip(got, want):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0, atol=0)


def _walk_eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs in its parameters."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _walk_eqns(sub)


def _block_solve_lowered(n, bs, num_blocks, num_iter, k=3):
    """``_block_solve`` traced at ``num_blocks`` equal blocks, and the
    forms that trace counted."""
    import jax
    import jax.numpy as jnp

    from keystone_tpu.nodes.learning.linear import _block_solve_for
    from keystone_tpu.parallel.mesh import get_mesh

    d = bs * num_blocks
    S, f32 = jax.ShapeDtypeStruct, jnp.float32
    bounds = tuple((i, min(d, i + bs)) for i in range(0, d, bs))
    before = bcd_form_counters()
    traced = _block_solve_for(get_mesh()).trace(
        S((n, d), f32), S((n, k), f32), S((d,), f32), S((k,), f32),
        S((n,), jnp.bool_), 0.0, bounds, num_iter)
    after = bcd_form_counters()
    return traced, {f: after[f] - before[f] for f in after}


def test_block_solve_sweeps_the_design_matrix_in_place():
    """What the chip's gain rests on, held on the CPU: at 8 equal
    blocks and one pass ``_block_solve`` is ONE loop, no conditional
    chooses among the blocks (the recovery's two-way ``cond`` is the
    only one), and XLA's temporaries stay far under the design
    matrix's size: no centred copy of it is made."""
    n, bs, B = 520, 24, 8
    traced, counted = _block_solve_lowered(n, bs, B, num_iter=1)
    assert counted == {"sliced": 1.0, "listed": 0.0, "unrolled": 0.0}
    eqns = list(_walk_eqns(traced.jaxpr.jaxpr))
    names = [eqn.primitive.name for eqn in eqns]
    assert names.count("scan") + names.count("while") == 1
    conds = [len(eqn.params["branches"]) for eqn in eqns
             if eqn.primitive.name == "cond"]
    assert set(conds) == {2}, conds
    whole = [eqn for eqn in eqns for v in eqn.outvars
             if getattr(v.aval, "shape", None) == (n, bs * B)]
    assert not whole, whole
    compiled = traced.lower().compile()
    text = compiled.as_text()
    assert text.count(" while(") == 1, text.count(" while(")
    assert text.count(" conditional(") == 1, text.count(" conditional(")
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < n * bs * B * 4 // 2, (temp, n * bs * B * 4)


@pytest.mark.parametrize("num_blocks,bs,num_iter,form,loops", [
    (8, 24, 1, "sliced", 1), (8, 24, 3, "sliced", 3), (4, 24, 1, "sliced", 1),
    (3, 24, 1, "unrolled", 0), (3, 24, 2, "unrolled", 0)])
def test_block_solve_counts_the_form_the_shapes_chose(
        num_blocks, bs, num_iter, form, loops):
    """Equal widths and at least 4 blocks sweep (one loop for one pass;
    the passes' loop and its blocks' loop beside it for more); fewer
    blocks unroll."""
    traced, counted = _block_solve_lowered(200 + num_blocks, bs, num_blocks,
                                           num_iter)
    assert counted == {f: float(f == form) for f in counted}
    names = [eqn.primitive.name for eqn in _walk_eqns(traced.jaxpr.jaxpr)]
    assert names.count("scan") == loops


def test_block_solve_unrolls_ragged_blocks():
    """A last block narrower than the rest keeps the unrolled body, as
    the estimator's bounds make it when the width does not divide."""
    import jax.numpy as jnp

    from keystone_tpu.nodes.learning.linear import block_least_squares
    from keystone_tpu.ops import linalg

    X, Y, mask, n, _ = sweep_problem(3, False, 5)
    bounds = tuple((i, min(80, i + 18)) for i in range(0, 80, 18))
    assert len(bounds) == 5 and bounds[-1] == (72, 80)
    before = bcd_form_counters()
    got, _, _ = block_least_squares(
        jnp.asarray(X), jnp.asarray(Y), n, 0.05, bounds, 2,
        mask=jnp.asarray(mask))
    after = bcd_form_counters()
    assert {f: after[f] - before[f] for f in after} == {
        "sliced": 0.0, "listed": 0.0, "unrolled": 1.0}
    blocks, Yc = centred_blocks(X, Y, mask, n, bounds)
    want = linalg._bcd_core_body(blocks, Yc, jnp.float32(0.05), num_passes=2)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
