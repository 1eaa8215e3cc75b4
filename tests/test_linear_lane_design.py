"""The linear lanes' designs (``linear._LaneDesign``), in both forms of
``linear._lane_design_form``: "shared", where every lane of a fold-grid
program reads one standardized matrix, its fold's mean and scale folded into
its coefficients (an accelerator's), and "per_lane", a copy of the design a
lane (a CPU's). Held here against the per-lane standardization the shared
form replaced, kept in this file as the reference; by the shapes of the
compiled programs; and by a lane's bits, whatever number of lanes shares
its program."""
import re

import jax.numpy as jnp
import numpy as np
import pytest

from transmogrifai_tpu.models import linear
from transmogrifai_tpu.observability import trace as package_trace
from transmogrifai_tpu.parallel import cv

N, D, FOLDS = 600, 10, 3
#: every point with an L1 share, so the squared kind runs FISTA too
GRID = np.array([[0.01, 0.5], [0.1, 0.1], [0.05, 0.5], [0.2, 0.1],
                 [0.02, 0.1], [0.3, 0.5], [0.005, 0.1], [0.08, 0.5]])
#: kind -> (classes, the metric of its eval kernel)
KINDS = {"logistic": (None, ("binary", "AuPR")),
         "svc": (None, ("binary", "AuPR")),
         "squared": (None, ("regression", "RootMeanSquaredError")),
         "softmax": (3, ("multiclass", "F1"))}
#: a one-hot column whose 1s all lie in fold 0's held-out rows, and a
#: column constant on every row
ABSENT, CONSTANT = 8, 9


def table():
    """Columns with means of +-100 and scales from 0.5 to 50, ABSENT and
    CONSTANT; a label of each kind; the folds' training masks."""
    rng = np.random.default_rng(11)
    held = rng.permutation(N) % FOLDS
    X = (rng.normal(size=(N, D)) * rng.uniform(0.5, 50.0, D)
         + rng.choice([-100.0, 100.0], D))
    X[:, CONSTANT] = 7.3
    X[:, ABSENT] = ((held == 0) & (rng.random(N) < 0.4)).astype(float)
    Z = (X - X.mean(0)) / np.where(X.std(0) > 0, X.std(0), 1.0)
    m = Z[:, :4] @ np.array([1.0, -0.8, 0.5, 0.3]) + 0.9 * Z[:, ABSENT]
    binary = (rng.random(N) < 1.0 / (1.0 + np.exp(-m))).astype(float)
    labels = {"logistic": binary, "svc": binary,
              "squared": m + rng.normal(size=N),
              "softmax": np.digitize(m + 0.5 * rng.normal(size=N),
                                     [-0.5, 0.5]).astype(float)}
    masks = (held[None, :] != np.arange(FOLDS)[:, None]).astype(float)
    return X, labels, masks, held


def per_lane_design(X, w, standardize, axis_name):
    """The reference: the weighted standardization each lane made of the
    whole design before the shared form (one (n, d) copy a lane), as a
    ``_LaneDesign`` whose statistics are 0 and 1."""
    wsum = jnp.maximum(jnp.sum(w), 1e-12)
    mu = jnp.sum(X * w[:, None], axis=0) / wsum
    sigma = jnp.sqrt(jnp.sum(w[:, None] * (X - mu) ** 2, axis=0) / wsum)
    safe = jnp.where(sigma > 1e-9 * jnp.maximum(jnp.abs(mu), 1.0), sigma,
                     1.0)
    d = X.shape[1]
    return linear._LaneDesign((X - mu) / safe, jnp.zeros(d, X.dtype),
                              jnp.ones(d, X.dtype), mu, safe, wsum)


def clear_kernels():
    cv._local_kernel.cache_clear()
    cv._local_eval_kernel.cache_clear()


@pytest.fixture
def fresh_kernels():
    """The fold-grid kernels traced anew inside the test, and again after
    it, so no program of a patched core outlives the patch."""
    clear_kernels()
    yield
    clear_kernels()


def use(monkeypatch, form=None, design=None):
    """Cores that build their lanes' designs in ``form``, or by
    ``design``; the kernels traced anew."""
    if form is not None:
        monkeypatch.setattr(linear, "_lane_design_form", lambda: form)
    if design is not None:
        monkeypatch.setattr(linear, "_lane_design", design)
    clear_kernels()


def held_out_margins(params, X, held, k):
    """(F, G, rows, classes) margins of every lane on its fold's held-out
    rows, from the parameters in the raw columns' space."""
    out = []
    for f in range(FOLDS):
        Xh = X[held == f]
        lanes = params[f].reshape(len(GRID), k or 1, D + 1)
        out.append([Xh @ p[:, :D].T + p[:, D] for p in lanes])
    return np.asarray(out)


@pytest.mark.parametrize("form", ["shared", "per_lane"])
@pytest.mark.parametrize("kind", KINDS)
def test_each_form_is_the_per_lane_standardization(kind, form,
                                                   fresh_kernels,
                                                   monkeypatch):
    """Every lane's coefficients and held-out margins, in either form, are
    the reference's within 1e-5 of their largest value; the constant
    column's coefficient is 0 in every lane, the absent one-hot column's in
    every lane of fold 0 (and not in the folds whose rows hold it)."""
    X, labels, masks, held = table()
    k = KINDS[kind][0]

    def fit():
        return cv.fit_linear_fold_grid(kind, X, labels[kind], masks, GRID,
                                       max_iter=20, k=k)
    use(monkeypatch, form=form)
    got = fit()
    use(monkeypatch, design=per_lane_design)
    want = fit()
    assert np.abs(got - want).max() <= 1e-5 * np.abs(want).max()
    margins = held_out_margins(got, X, held, k)
    reference = held_out_margins(want, X, held, k)
    assert np.abs(margins - reference).max() <= (
        1e-5 * np.abs(reference).max())
    coef = got[..., :D]
    # the shared form's scale 0 is an exact 0; the per-lane copy's column
    # keeps the rounding of ``X - mu``
    zero = 0.0 if form == "shared" else 1e-12 * np.abs(coef).max()
    assert np.all(np.abs(coef[..., CONSTANT]) <= zero)
    assert np.all(np.abs(coef[0, ..., ABSENT]) <= zero)
    assert np.all(np.abs(coef[1:, ..., ABSENT]).max(axis=-1) > 1e-3)


def _kernel_args(kind, lanes, X, labels, masks, held):
    """The eval kernel's arguments for ``lanes`` lanes, fold-major."""
    reps = lanes // FOLDS
    nv = N // FOLDS
    Xv = np.stack([X[held == f][:nv] for f in range(FOLDS)])
    yv = np.stack([labels[kind][held == f][:nv] for f in range(FOLDS)])
    return (jnp.asarray(np.repeat(masks, reps, axis=0)),
            jnp.asarray(np.tile(GRID[:reps, 0], FOLDS)),
            jnp.asarray(np.tile(GRID[:reps, 1], FOLDS)),
            jnp.asarray(np.repeat(np.arange(FOLDS), reps).astype(np.int32)),
            jnp.asarray(X), jnp.asarray(labels[kind]), jnp.asarray(Xv),
            jnp.asarray(yv))


def lane_copies(kind, lanes=6):
    """The shapes in the compiled eval kernel of ``lanes`` lanes that hold
    (lanes, n, d) elements in any order: a copy of the design a lane."""
    X, labels, masks, held = table()
    k, spec = KINDS[kind]
    kernel = cv._local_eval_kernel(
        cv._kernel_cfg(kind, True, True, True, 20, k), spec)
    text = kernel.lower(*_kernel_args(kind, lanes, X, labels, masks,
                                      held)).compile().as_text()
    shapes = {tuple(int(x) for x in dims.split(","))
              for dims in re.findall(r"\[(\d+(?:,\d+)+)\]", text)}
    return {s for s in shapes if sorted(s) == sorted((lanes, N, D))}


@pytest.mark.parametrize("kind", KINDS)
def test_no_lane_copies_the_shared_design(kind, fresh_kernels,
                                          monkeypatch):
    """The compiled fold-grid program of each kind's FISTA path holds no
    (L, n, d) array in the shared form; the per-lane form's does, so the
    check sees one where it is."""
    use(monkeypatch, form="shared")
    assert lane_copies(kind) == set()
    use(monkeypatch, form="per_lane")
    assert lane_copies(kind)


def test_the_form_follows_the_backend(monkeypatch):
    assert linear._lane_design_form() == "per_lane"
    monkeypatch.setattr(linear.jax, "default_backend", lambda: "tpu")
    assert linear._lane_design_form() == "shared"


@pytest.mark.parametrize("few", [2, 8])
@pytest.mark.parametrize("form", ["per_lane", "shared"])
@pytest.mark.parametrize("kind", KINDS)
def test_a_lane_in_any_company(kind, form, few, fresh_kernels, monkeypatch):
    """The first lanes of a 24-lane program against a program that runs
    only them: bitwise the same in the per-lane form, the one a CPU takes
    (a search mesh's shards run fewer lanes a program than one device);
    the same to rounding in the shared form, whose products a CPU computes
    by another emitter at another width."""
    X, labels, masks, _ = table()
    k = KINDS[kind][0]
    use(monkeypatch, form=form)
    kernel = cv._local_kernel(cv._kernel_cfg(kind, True, True, True, 20, k))
    wmat = np.repeat(masks, len(GRID), axis=0)
    regs, alphas = np.tile(GRID[:, 0], FOLDS), np.tile(GRID[:, 1], FOLDS)

    def run(lanes):
        return np.asarray(kernel(jnp.asarray(wmat[:lanes]),
                                 jnp.asarray(regs[:lanes]),
                                 jnp.asarray(alphas[:lanes]), jnp.asarray(X),
                                 jnp.asarray(labels[kind])))
    assert len(wmat) == 24
    alone, among = run(few), run(24)[:few]
    if form == "per_lane":
        np.testing.assert_array_equal(alone, among)
    else:
        assert np.abs(alone - among).max() <= 1e-12 * np.abs(among).max()


def test_fetch_span_carries_the_lane_design():
    """The linear fold-grid functions' ``search.fetch`` span names the form
    of the lanes' design its program holds (``design``, from the pure
    ``_lane_design_form``: a CPU's ``per_lane``) and the call's
    ``lanes``."""
    X, labels, masks, _ = table()
    package_trace.configure(True)
    package_trace.reset()
    try:
        cv.fit_linear_fold_grid("logistic", X, labels["logistic"], masks,
                                GRID[:2], max_iter=20)
        spans = [s for s in package_trace.spans()
                 if s["name"] == "search.fetch"]
    finally:
        package_trace.configure(False)
        package_trace.reset()
    assert len(spans) == 1
    attrs = spans[0]["attrs"]
    assert attrs["lanes"] == FOLDS * 2
    assert attrs["design"] == "per_lane"
    assert not [k for k in attrs if k.startswith("design_")]
