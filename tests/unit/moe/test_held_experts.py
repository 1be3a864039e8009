"""The expert layer that is told which experts it holds: sigmoid top-k
routing with a selection bias, no capacity and no dropped token, the shares
of an expert-parallel deployment adding up to the whole layer, and the
grouped matmul (Pallas, in interpret mode here) against plain matmuls."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.moe import held_experts as he
from deepspeed_tpu.ops.pallas.grouped_matmul import grouped_matmul

N, D, F, E, K = 37, 32, 48, 16, 4


@pytest.fixture(scope="module")
def layer():
    rs = np.random.RandomState(0)
    f = lambda *shape, scale=1.0: jnp.asarray(rs.randn(*shape) * scale, jnp.float32)
    return dict(h=f(N, D), gate=f(D, E, scale=0.3), bias=f(E, scale=0.01),
                experts={"wg": f(E, D, F, scale=0.2), "wi": f(E, D, F, scale=0.2),
                         "wo": f(E, F, D, scale=0.2)})


def dense(layer, chosen, weights, first, count):
    """Every held expert on every token, weighed by its routing weight or zero."""
    h, ex = layer["h"], layer["experts"]
    out = jnp.zeros((N, D))
    for e in range(first, first + count):
        y = (jax.nn.silu(h @ ex["wg"][e]) * (h @ ex["wi"][e])) @ ex["wo"][e]
        out += y * jnp.where(chosen == e, weights, 0).sum(1)[:, None]
    return out


def share(layer, first, count):
    return {n: w[first:first + count] for n, w in layer["experts"].items()}


def test_route_scores_by_sigmoid_chooses_by_score_plus_bias_and_normalises(layer):
    chosen, weights = he.route(layer["h"], layer["gate"], layer["bias"], K)
    scores = 1 / (1 + np.exp(-np.asarray(layer["h"] @ layer["gate"], np.float64)))
    want = np.argsort(-(scores + np.asarray(layer["bias"])), axis=1)[:, :K]
    assert (np.sort(np.asarray(chosen), 1) == np.sort(want, 1)).all()
    picked = np.take_along_axis(scores, np.asarray(chosen), axis=1)
    assert np.allclose(weights, picked / picked.sum(1, keepdims=True), atol=1e-6)
    assert np.allclose(np.asarray(weights).sum(1), 1.0, atol=1e-6)  # the bias enters no weight


def test_the_bias_decides_a_choice_and_no_weight(layer):
    tilted = layer["bias"].at[3].add(10.0)
    chosen, weights = he.route(layer["h"], layer["gate"], tilted, K)
    assert (np.asarray(chosen) == 3).any(1).all()  # every token takes expert 3 now
    w3 = np.asarray(jnp.where(chosen == 3, weights, 0).sum(1))
    assert (w3 < 0.9).all()  # ... at its unbiased score's weight


def test_router_runs_in_float32_whatever_the_weights_are_stored_in(layer):
    chosen, weights = he.route(layer["h"].astype(jnp.bfloat16), layer["gate"].astype(jnp.bfloat16),
                               layer["bias"].astype(jnp.bfloat16), K)
    assert weights.dtype == jnp.float32 and chosen.dtype == jnp.int32


@pytest.mark.parametrize("grad", [False, True])
@pytest.mark.parametrize("first,count", [(0, 16), (4, 4), (12, 4), (7, 1)])
def test_held_share_is_the_held_experts_part_of_the_layer(layer, grad, first, count):
    chosen, weights = he.route(layer["h"], layer["gate"], layer["bias"], K)
    out, counts = he.held_experts_ffn(layer["h"], chosen, weights, share(layer, first, count),
                                      first, count, grad=grad, tm=8)
    assert np.allclose(out, dense(layer, chosen, weights, first, count), atol=2e-5)
    want = [(np.asarray(chosen) == e).sum() for e in range(first, first + count)]
    assert list(np.asarray(counts)) == want


@pytest.mark.parametrize("shares", [16, 4, 2])
def test_the_shares_add_up_to_the_whole_layer(layer, shares):
    chosen, weights = he.route(layer["h"], layer["gate"], layer["bias"], K)
    count = E // shares
    total = sum(he.held_experts_ffn(layer["h"], chosen, weights, share(layer, s * count, count),
                                    s * count, count, tm=8)[0]
                for s in range(shares))
    whole = he.held_experts_ffn(layer["h"], chosen, weights, layer["experts"], 0, E, tm=8)[0]
    assert np.allclose(total, whole, atol=5e-5)
    assert np.allclose(whole, dense(layer, chosen, weights, 0, E), atol=5e-5)


@pytest.mark.parametrize("grad", [False, True])
def test_no_token_is_dropped_when_every_token_takes_one_expert(layer, grad):
    skewed = layer["bias"].at[jnp.array([5, 6, 7, 9])].add(10.0)  # all N tokens to four experts
    chosen, weights = he.route(layer["h"], layer["gate"], skewed, K)
    out, counts = he.held_experts_ffn(layer["h"], chosen, weights, share(layer, 4, 4), 4, 4,
                                      grad=grad, tm=8)
    assert list(np.asarray(counts)) == [0, N, N, N]  # experts 4..7: three of the four chosen
    assert np.allclose(out, dense(layer, chosen, weights, 4, 4), atol=5e-5)


def test_a_token_that_is_not_valid_is_neither_computed_nor_counted(layer):
    chosen, weights = he.route(layer["h"], layer["gate"], layer["bias"], K)
    valid = jnp.arange(N) % 3 != 0
    out, counts = he.held_experts_ffn(layer["h"], chosen, weights, layer["experts"], 0, E,
                                      valid=valid, tm=8)
    want = dense(layer, chosen, weights, 0, E)
    assert np.allclose(out[valid], want[valid], atol=5e-5) and not np.asarray(out[~valid]).any()
    assert int(counts.sum()) == int(valid.sum()) * K


@pytest.mark.parametrize("tm", [8, 16, 128])
def test_layout_pads_each_expert_to_whole_tiles_and_names_each_tiles_expert(layer, tm):
    chosen, _ = he.route(layer["h"], layer["gate"], layer["bias"], K)
    lay = he.layout(chosen, 4, 8, tm)
    M = he.buffer_rows(N, K, 8, tm)
    assert lay.src.shape == (M,) and M % tm == 0
    counts = np.asarray(lay.counts)
    assert int(lay.num_tiles[0]) == sum(-(-c // tm) for c in counts)
    src, dest = np.asarray(lay.src), np.asarray(lay.dest)
    for n in range(N):
        for j in range(K):
            e = int(chosen[n, j]) - 4
            if 0 <= e < 8:
                assert src[dest[n, j]] == n and lay.tile_group[dest[n, j] // tm] == e
            else:
                assert dest[n, j] == M
    assert (src < N).sum() == counts.sum()  # every other row is the zero row


def test_buffer_holds_the_worst_routing():
    assert he.buffer_rows(32, 8, 16, 16) == 32 * 8 + 16 * 15 + 0  # already whole tiles
    assert he.buffer_rows(10, 8, 2, 8) == 40  # a token takes at most the 2 held experts


@pytest.mark.parametrize("layer_index", [None, 1])
def test_grouped_matmul_reads_the_groups_named_and_skips_unused_tiles(layer_index):
    rs = np.random.RandomState(1)
    G, tm, Kd, Nd = 3, 8, 16, 24
    w = jnp.asarray(rs.randn(2, G, Kd, Nd), jnp.float32)
    x = jnp.asarray(rs.randn(6 * tm, Kd), jnp.float32)
    groups = jnp.asarray([0, 0, 2, 2, 2, 1], jnp.int32)   # the last two tiles are not in use
    out = grouped_matmul(x, w if layer_index is not None else w[0], groups,
                         jnp.asarray([4], jnp.int32), tm=tm, layer=layer_index)
    wl = w[layer_index or 0]
    for t in range(4):
        assert np.allclose(out[t * tm:(t + 1) * tm], x[t * tm:(t + 1) * tm] @ wl[int(groups[t])],
                           atol=1e-5)


def test_the_xla_form_has_a_gradient(layer):
    chosen, weights = he.route(layer["h"], layer["gate"], layer["bias"], K)

    def loss(ex):
        return he.held_experts_ffn(layer["h"], chosen, weights, ex, 0, E, grad=True, tm=1)[0].sum()

    g = jax.grad(loss)(layer["experts"])
    assert all(np.isfinite(np.asarray(v)).all() and float(jnp.abs(v).sum()) > 0 for v in g.values())


@pytest.mark.parametrize("score", ["sigmoid", "softmax"])
def test_a_routed_scaling_factor_multiplies_the_normalised_weights_and_nothing_else(layer, score):
    bias = layer["bias"] if score == "sigmoid" else None
    chosen, weights = he.route(layer["h"], layer["gate"], bias, K, score)
    scaled_chosen, scaled = he.route(layer["h"], layer["gate"], bias, K, score, scale=1.8)
    assert np.array_equal(chosen, scaled_chosen)
    assert np.allclose(scaled, 1.8 * weights, rtol=1e-6) and np.allclose(scaled.sum(1), 1.8, rtol=1e-5)
    # at 1.0 the function is the one it was: the same program, to the text
    text = lambda **kw: jax.jit(lambda h, g: he.route(h, g, bias, K, score, **kw)).lower(
        layer["h"], layer["gate"]).as_text()
    assert text() == text(scale=1.0) != text(scale=1.8)


# ---- the serving path's way back: a token's k rows summed over a leading assignment axis ---------

@pytest.mark.parametrize("held", ["half", "all"])
@pytest.mark.parametrize("masked", [False, True], ids=["every-row", "parked-rows"])
@pytest.mark.parametrize("k,n,dtype", [
    (4, 37, jnp.float32), (8, 48, jnp.float32), (10, 37, jnp.float32), (22, 32, jnp.float32),
    (10, 48, jnp.bfloat16), (22, 37, jnp.bfloat16)], ids=lambda v: getattr(v, "__name__", str(v)))
def test_the_ticks_combine_is_every_held_expert_on_every_token(k, n, dtype, masked, held):
    """The serving layer (``grad=False``) against every held expert on every token, at the top-k of
    the plans served (4 GLM, 8 MiMo, 10 Granite and Qwen3-Next, 22 Nemotron), tokens a multiple of
    the 16-row tile and not: float32 to 2e-5, bfloat16 within its rounding of the k-term sum. A
    token left out adds exactly 0, and so does an assignment to an expert not held."""
    n_experts, d, f, tm = 32, 32, 24, 8
    first, count = (8, 16) if held == "half" else (0, n_experts)
    rs = np.random.RandomState(1000 * k + n)
    draw = lambda *shape, scale=1.0: jnp.asarray(rs.randn(*shape) * scale, jnp.float32)
    h, gate, bias = draw(n, d), draw(d, n_experts, scale=0.3), draw(n_experts, scale=0.01)
    ex = {"wg": draw(count, d, f, scale=0.2), "wi": draw(count, d, f, scale=0.2),
          "wo": draw(count, f, d, scale=0.2)}
    valid = jnp.asarray(np.arange(n) % 5 != 2) if masked else None

    def by_every_expert(h, ex, chosen, weights):
        y = jnp.einsum("enf,efd->end", jax.nn.silu(jnp.einsum("nd,edf->enf", h, ex["wg"]))
                       * jnp.einsum("nd,edf->enf", h, ex["wi"]), ex["wo"])
        theirs = jnp.where(chosen[None] == first + jnp.arange(count)[:, None, None], weights[None], 0).sum(2)
        out = (y * theirs[:, :, None]).sum(0)
        return out if valid is None else out * valid[:, None]

    @jax.jit
    def both(h, ex):                                      # one program a case: the routing, both sides
        chosen, weights = he.route(h, gate, bias, k)
        h, ex = jax.tree.map(lambda a: a.astype(dtype), (h, ex))           # as stored
        got = he.held_experts_ffn(h, chosen, weights, ex, first, count, valid=valid, tm=tm)
        want = by_every_expert(*jax.tree.map(lambda a: a.astype(jnp.float32), (h, ex)), chosen, weights)
        return got, want, chosen

    (out, counts), want, chosen = both(h, ex)
    assert out.dtype == dtype and out.shape == (n, d)
    # bfloat16: each of the k rows is rounded once where the buffer stores it, the sum once more
    atol = 2e-5 if dtype == jnp.float32 else 2.0 ** -7 * float(jnp.abs(want).max())
    assert np.abs(np.asarray(out, np.float32) - np.asarray(want)).max() <= atol
    chosen = np.asarray(chosen)
    seen = chosen[np.ones(n, bool) if valid is None else np.asarray(valid)]
    assert list(np.asarray(counts)) == [(seen == e).sum() for e in range(first, first + count)]
    if masked:
        assert not np.asarray(out[~valid], np.float32).any()            # exactly 0, not a rounding
    if held == "half":
        # tokens none of whose experts are held: every one of their k assignments adds exactly 0
        away = ~((chosen >= first) & (chosen < first + count)).any(1)
        assert not np.asarray(out, np.float32)[away].any()


def test_a_row_of_an_unused_tile_adds_nothing_whatever_it_holds():
    """Rows past the tiles in use hold whatever the kernel left there; ``dest`` names none of them
    for a held assignment, and a not-held one (``dest == M``) is clipped onto the LAST row, which
    may hold anything: NaN there must not reach a token."""
    y = jnp.full((24, 8), jnp.nan, jnp.float32).at[:3].set(jnp.arange(24.0).reshape(3, 8))
    dest = jnp.asarray([[0, 24, 24], [24, 1, 2], [24, 24, 24]], jnp.int32)
    w = jnp.asarray([[0.5, 0.3, 0.2], [0.1, 0.6, 0.3], [0.2, 0.3, 0.5]], jnp.float32)
    out = np.asarray(he._combine(y, dest, w))
    assert out.dtype == np.float32 and np.array_equal(out[2], np.zeros(8))
    assert np.allclose(out[0], 0.5 * np.arange(8)) and np.allclose(
        out[1], 0.6 * np.arange(8, 16) + 0.3 * np.arange(16, 24))


# ---- the training path: a bucket of the sorted buffer, the whole buffer as the fallback ----------

BN, BK, BFIRST, BCOUNT, BTM = 64, 2, 4, 2, 8   # bucket 48 rows (six tiles), whole buffer 144


def routed(c0, c1):
    """(chosen, weights): the first ``c0`` tokens take held expert 4, the next ``c1`` held expert
    5, each beside an expert that is not held; every other token takes two that are not."""
    rs = np.random.RandomState(c0 * 100 + c1)
    chosen = np.stack([rs.randint(6, E, BN), rs.randint(0, 4, BN)], axis=1)
    chosen[:c0, 0], chosen[c0:c0 + c1, 1] = 4, 5
    weights = rs.rand(BN, BK) + 0.1
    return jnp.asarray(chosen, jnp.int32), jnp.asarray(weights / weights.sum(1, keepdims=True), jnp.float32)


@pytest.mark.parametrize("c0,c1,masked,tiles", [
    (10, 9, False, 4), (24, 24, False, 6), (25, 24, False, 7), (BN, 0, False, 8), (30, 30, True, 6)],
    ids=["fits", "fills-the-bucket", "one-tile-past", "all-to-one-expert", "valid-mask"])
def test_the_bucket_and_the_whole_buffer_are_one_layer(layer, c0, c1, masked, tiles):
    """``grad=True`` over the bucket, or over the whole buffer when the routing's padded rows end
    past it, against the whole-buffer body and against every held expert on every token: the
    output, the gradients of ``h`` and of the routing weights, and the experts' own."""
    assert he.bucket_rows(BN, BK, BCOUNT, E, BTM) == 48 < he.buffer_rows(BN, BK, BCOUNT, BTM) == 144
    h, ex = layer["h"][:1].repeat(BN, 0) * jnp.linspace(0.5, 1.5, BN)[:, None], share(layer, BFIRST, BCOUNT)
    chosen, weights = routed(c0, c1)
    valid = (jnp.arange(BN) % 4 != 1) if masked else None
    lay = he.layout(chosen, BFIRST, BCOUNT, BTM, valid)
    assert int(lay.num_tiles[0]) == tiles            # six tiles is what the bucket holds
    probe = jnp.asarray(np.random.RandomState(3).randn(BN, D), jnp.float32)

    def by_bucket(h, weights, ex):
        return he.held_experts_ffn(h, chosen, weights, ex, BFIRST, BCOUNT, grad=True, valid=valid,
                                   tm=BTM, n_experts=E)[0]

    def by_whole_buffer(h, weights, ex):
        return he._rows_ffn(lay.src.shape[0], BFIRST, BTM, h, chosen, weights, ex, lay)

    def by_every_expert(h, weights, ex):
        out = jnp.zeros((BN, D))
        for e in range(BCOUNT):
            y = (jax.nn.silu(h @ ex["wg"][e]) * (h @ ex["wi"][e])) @ ex["wo"][e]
            out += y * jnp.where(chosen == BFIRST + e, weights, 0).sum(1)[:, None]
        return out if valid is None else out * valid[:, None]

    run = lambda fn: jax.jit(jax.value_and_grad(
        lambda *a: (fn(*a) * probe).sum(), argnums=(0, 1, 2)))(h, weights, ex)
    got, whole, dense_ = run(jax.checkpoint(by_bucket)), run(by_whole_buffer), run(by_every_expert)
    assert "stablehlo.case" in jax.jit(by_bucket).lower(h, weights, ex).as_text()
    gap = lambda a, b: float(jnp.abs(a - b).max() / jnp.abs(b).max())
    # float32's last digit against the whole buffer (the products' row counts differ, nothing else)
    assert max(map(gap, jax.tree.leaves(got), jax.tree.leaves(whole))) <= 1e-6
    assert max(map(gap, jax.tree.leaves(got), jax.tree.leaves(dense_))) <= 1e-5
    out, counts = he.held_experts_ffn(h, chosen, weights, ex, BFIRST, BCOUNT, grad=True, valid=valid,
                                      tm=BTM, n_experts=E)
    held = np.asarray(chosen)[np.ones(BN, bool) if valid is None else np.asarray(valid)]
    assert list(np.asarray(counts)) == [(held == 4).sum(), (held == 5).sum()]   # no token dropped
    assert np.allclose(out, by_every_expert(h, weights, ex), atol=2e-5)


def test_a_bucket_no_smaller_than_the_buffer_is_one_branch_and_a_tick_is_the_program_it_was(layer):
    chosen, weights = he.route(layer["h"], layer["gate"], layer["bias"], K)
    assert he.bucket_rows(N, K, 4, E, 8) == 104 and he.bucket_rows(N, K, 8, E, 8) >= he.buffer_rows(N, K, 8, 8)
    text = lambda count, **kw: jax.jit(lambda h, w, ex: he.held_experts_ffn(
        h, chosen, w, ex, 4, count, tm=8, **kw)).lower(layer["h"], weights, share(layer, 4, count)).as_text()
    assert "stablehlo.case" in text(4, grad=True, n_experts=E)           # 104 of 176 rows: two branches
    assert "stablehlo.case" not in text(8, grad=True, n_experts=E)       # half the experts held: one
    assert "stablehlo.case" not in text(4, grad=True)                    # a model held whole: one
    assert text(4) == text(4, n_experts=E)

    assert hashlib.sha256(tick_text().encode()).hexdigest() == PARENTS_TICK


# sha256 of the serving form's lowered text (a layer of a stack read by the kernel, a ``valid``
# mask): what the training path's PRs must leave alone. Recorded in PR 52, which meant to change
# it (the way back over a leading assignment axis, ``_combine``); with the parent's three lines in
# ``_combine``'s place it was the text of 0c30f23, the parent of the PR that brought the bucket
PARENTS_TICK = "8667aea09e4fd19317f421599209fa8f9ad1d5128428918dc2cce1f40eb7ddc2"


def tick_text():
    def tick(h, gate, bias, valid, ex):
        picked, w = he.route(h, gate, bias, K)
        return he.held_experts_ffn(h, picked, w, ex, 4, 4, valid=valid, tm=8, layer=jnp.int32(1))

    f = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)
    stack = {"wg": f(2, 4, D, F), "wi": f(2, 4, D, F), "wo": f(2, 4, F, D)}
    return jax.jit(tick).lower(f(N, D), f(D, E), f(E), jax.ShapeDtypeStruct((N,), jnp.bool_),
                               stack).as_text()


def test_the_fallback_runs_under_a_scope_of_its_own_forward_and_backward(layer):
    """A traced run's scope table tells the branches apart: what the whole buffer runs, in the
    forward and in the backward, reads ``moe.experts.whole``; the bucket's reads ``moe.experts``."""
    from deepspeed_tpu.telemetry.hlo_scopes import Scope, model_scope, scope_table

    chosen, weights = routed(10, 9)
    h = jnp.ones((BN, D), jnp.float32)
    loss = lambda h, w, ex: he.held_experts_ffn(h, chosen, w, ex, BFIRST, BCOUNT, grad=True, tm=BTM,
                                                n_experts=E)[0].sum()
    compiled = jax.jit(jax.value_and_grad(jax.checkpoint(loss), argnums=(0, 1, 2))).lower(
        h, weights, share(layer, BFIRST, BCOUNT)).compile()
    paths = set(scope_table(compiled).values())
    of = lambda branch: {model_scope(p) for p in paths if f"/cond/{branch}/" in p}
    assert of("branch_0_fun") == {Scope.MOE_EXPERTS_WHOLE}      # predicate false: the whole buffer
    assert of("branch_1_fun") == {Scope.MOE_EXPERTS}
    assert any("transpose" in p for p in paths if Scope.MOE_EXPERTS_WHOLE in p)
