"""``models/moe.RoutedExperts``: a chip's share of a sparse feed-forward
layer, against a dense loop over the experts; no token is ever dropped; the
shares of all the chips add up to the whole layer; and only the blocks of
sorted rows that hold a row are worked off, in both passes."""

import jax
import jax.numpy as jnp
import pytest

from tpucfn.models.moe import RoutedExperts

E, K, F, D, T = 8, 2, 12, 16, 40


def layer(first, count, shared=F):
    return RoutedExperts(E, K, F, (first, count), shared_dim=shared,
                         dtype=jnp.float32)


def make(first, count, *, skew=True, seed=0):
    m = layer(first, count)
    x = jax.random.normal(jax.random.key(seed + 1), (T, D))
    params = jax.tree.map(lambda a: 10 * a,
                          m.init(jax.random.key(seed), x)["params"])
    if skew:   # expert 3 takes most tokens, expert 2 none
        x = x.at[:, 0].set(1.0)
        r = params["router"]["kernel"]
        params["router"]["kernel"] = r.at[0, 3].add(3.0).at[0, 2].add(-100.0)
    return m, params, x


def swiglu(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd


def dense_loop(params, x, first, count, shared=True):
    """Every held expert on every token, the router's renormalised weights
    as masks."""
    p = jax.nn.softmax(x @ params["router"]["kernel"], -1)
    g, idx = jax.lax.top_k(p, K)
    g = g / g.sum(-1, keepdims=True)
    ex = params["experts"]
    out = jnp.zeros_like(x)
    for e in range(count):
        w = jnp.sum(jnp.where(idx == first + e, g, 0.0), -1)
        out = out + w[:, None] * swiglu(
            x, ex["gate_proj"]["kernel"][e], ex["up_proj"]["kernel"][e],
            ex["down_proj"]["kernel"][e])
    if shared:
        se = params["shared_expert"]
        out = out + jax.nn.sigmoid(x @ params["shared_expert_gate"]["kernel"]) \
            * swiglu(x, se["gate_proj"]["kernel"], se["up_proj"]["kernel"],
                     se["down_proj"]["kernel"])
    return out


@pytest.mark.parametrize("first,count", [(0, 8), (2, 4), (4, 4), (3, 1)])
def test_values_and_gradients_match_a_dense_loop(first, count):
    m, params, x = make(first, count)
    out, stats = m.apply({"params": params}, x)
    ref = dense_loop(params, x, first, count)
    assert float(jnp.max(jnp.abs(out - ref))) <= 1e-5 * float(jnp.max(jnp.abs(ref)))
    # expert 2 is held in three of the cases and receives no token; expert 3
    # receives most
    counts = jnp.bincount(jax.lax.top_k(jax.nn.softmax(
        x @ params["router"]["kernel"], -1), K)[1].reshape(-1), length=E)
    assert int(counts[2]) == 0 and int(counts[3]) == int(jnp.max(counts))
    assert float(stats["rows"]) == float(jnp.sum(counts[first:first + count]))
    assert float(stats["dropped"]) == 0.0
    f = lambda fn: lambda p, x: jnp.sum(jnp.sin(fn(p, x)))  # noqa: E731
    got = jax.grad(f(lambda p, x: m.apply({"params": p}, x)[0]), (0, 1))(params, x)
    want = jax.grad(f(lambda p, x: dense_loop(p, x, first, count)), (0, 1))(params, x)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        # float32; a grouped product sums a group's rows in another order
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-4 * float(jnp.max(jnp.abs(b))) + 1e-6


def test_no_token_is_dropped_at_any_load():
    """Every assignment on one held expert: the static shapes are sized for
    it, and the counters say so."""
    m, params, x = make(0, 4, skew=False)
    params["router"]["kernel"] = jnp.zeros((D, E)).at[0, 1].set(
        100.0).at[0, 6].set(50.0)
    x = x.at[:, 0].set(1.0)       # every token: expert 1 first, expert 6 second
    out, stats = m.apply({"params": params}, x)
    assert float(stats["rows"]) == T            # one of each token's two
    assert float(stats["dropped"]) == 0.0
    assert float(stats["load_max_over_mean"]) == pytest.approx(4.0)
    ref = dense_loop(params, x, 0, 4)
    assert float(jnp.max(jnp.abs(out - ref))) <= 1e-5 * float(jnp.max(jnp.abs(ref)))
    # and a share that holds none of the chosen experts gives the shared part
    m2 = layer(2, 3)
    p2 = dict(params, experts=jax.tree.map(lambda a: a[:3], params["experts"]))
    out2, stats2 = m2.apply({"params": p2}, x)
    assert float(stats2["rows"]) == 0.0 and float(stats2["dropped"]) == 0.0
    assert bool(jnp.all(jnp.isfinite(out2)))


def test_the_shares_add_up_to_the_whole_layer():
    """Eight chips hold one expert each of a small layer.  Their routed parts
    summed, with the shared expert (which every chip computes alike) counted
    once, are what the uncut reference gives for the whole layer."""
    from benchmark.reference import qwen3_next as ref
    from benchmark.reference.numerics import Numerics

    _, params, x = make(0, E, skew=False, seed=3)
    model = {"num_experts_per_tok": K}
    whole = ref.sparse_ffn(model, Numerics(), x, params)   # all 8 held: uncut
    shared = dense_loop(params, x, 0, 0)                   # the shared part alone
    total = shared
    for chip in range(E):
        mine = dict(params, experts=jax.tree.map(
            lambda a: a[chip:chip + 1], params["experts"]))
        part, stats = layer(chip, 1).apply({"params": mine}, x)
        assert float(stats["dropped"]) == 0.0
        total = total + (part - shared)
        # the reference given the same share agrees with the program's part
        theirs = ref.sparse_ffn(model, Numerics(), x, mine, first=chip)
        assert float(jnp.max(jnp.abs(part - theirs))) <= 1e-5 * float(
            jnp.max(jnp.abs(theirs)))
    assert float(jnp.max(jnp.abs(total - whole))) <= 1e-5 * float(
        jnp.max(jnp.abs(whole)))


def test_what_it_holds_has_to_lie_inside_the_routers_width():
    x = jnp.zeros((4, D))
    for held in ((6, 4), (-1, 2), (0, 0)):
        with pytest.raises(ValueError):
            layer(*held).init(jax.random.key(0), x)


# ---- the second router: sigmoid scores, a selection bias, a weight scale ----

def sigmoid_layer(first, count):
    return RoutedExperts(E, K, F, (first, count), shared_dim=F,
                         dtype=jnp.float32, score="sigmoid", select_bias=True,
                         weight_scale=2.5, shared_gate=False)


def make_sigmoid(first, count, seed=0):
    m = sigmoid_layer(first, count)
    x = jax.random.normal(jax.random.key(seed + 1), (T, D))
    params = jax.tree.map(lambda a: 10 * a,
                          m.init(jax.random.key(seed), x)["params"])
    # scores spread over (0, 1), and a bias wide enough to move the chosen
    # set of most tokens
    params["router"]["kernel"] = 0.5 * jax.random.normal(
        jax.random.key(seed + 3), (D, E))
    params["e_score_correction_bias"] = 0.5 * jax.random.normal(
        jax.random.key(seed + 2), (E,))
    return m, params, x


def sigmoid_loop(params, x, first, count, *, choose_by="biased",
                 weigh_by="unbiased", shared=True):
    """Every held expert on every token; the chosen set by score plus bias,
    the weights the unbiased scores over their sum, times 2.5."""
    s = jax.nn.sigmoid(x @ params["router"]["kernel"])
    biased = s + params["e_score_correction_bias"]
    _, idx = jax.lax.top_k(biased if choose_by == "biased" else s, K)
    g = jnp.take_along_axis(biased if weigh_by == "biased" else s, idx, -1)
    g = 2.5 * g / (g.sum(-1, keepdims=True) + 1e-20)
    ex = params["experts"]
    out = jnp.zeros_like(x)
    for e in range(count):
        w = jnp.sum(jnp.where(idx == first + e, g, 0.0), -1)
        out = out + w[:, None] * swiglu(
            x, ex["gate_proj"]["kernel"][e], ex["up_proj"]["kernel"][e],
            ex["down_proj"]["kernel"][e])
    if shared:
        se = params["shared_expert"]
        out = out + swiglu(x, se["gate_proj"]["kernel"], se["up_proj"]["kernel"],
                           se["down_proj"]["kernel"])
    return out


@pytest.mark.parametrize("first,count", [(0, 8), (2, 4), (3, 1)])
def test_the_sigmoid_router_matches_a_dense_loop(first, count):
    m, params, x = make_sigmoid(first, count)
    assert "shared_expert_gate" not in params
    out, stats = m.apply({"params": params}, x)
    ref = sigmoid_loop(params, x, first, count)
    scale = float(jnp.max(jnp.abs(ref)))
    assert float(jnp.max(jnp.abs(out - ref))) <= 1e-5 * scale
    assert float(stats["dropped"]) == 0.0
    # the bias is no decoration: dropping it from the choice, or weighing by
    # the biased score, is another layer
    routed = float(jnp.max(jnp.abs(ref - sigmoid_loop(params, x, 0, 0))))
    for wrong in (dict(choose_by="unbiased"), dict(weigh_by="biased")):
        other = sigmoid_loop(params, x, first, count, **wrong)
        assert float(jnp.max(jnp.abs(out - other))) > 1e-2 * routed, wrong
    f = lambda fn: lambda p, x: jnp.sum(jnp.sin(fn(p, x)))  # noqa: E731
    got = jax.grad(f(lambda p, x: m.apply({"params": p}, x)[0]), (0, 1))(params, x)
    want = jax.grad(f(lambda p, x: sigmoid_loop(p, x, first, count)), (0, 1))(params, x)
    assert float(jnp.max(jnp.abs(got[0]["e_score_correction_bias"]))) == 0.0
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-4 * float(jnp.max(jnp.abs(b))) + 1e-6


def test_the_sigmoid_shares_add_up_to_the_whole_layer():
    """All 8 shares of one sparse layer of the latent decoder (one expert a
    chip): their routed parts summed, with the ungated shared expert counted
    once, are what the uncut reference gives for the whole layer."""
    from benchmark.reference import joyai_llm_flash as ref
    from benchmark.reference.numerics import Numerics

    _, params, x = make_sigmoid(0, E, seed=3)
    model = {"num_experts_per_tok": K, "routed_scaling_factor": 2.5}
    whole = ref.sparse_ffn(model, Numerics(), x, params)   # all 8 held: uncut
    shared = sigmoid_loop(params, x, 0, 0)                 # the shared part alone
    total = shared
    for chip in range(E):
        mine = dict(params, experts=jax.tree.map(
            lambda a: a[chip:chip + 1], params["experts"]))
        part, stats = sigmoid_layer(chip, 1).apply({"params": mine}, x)
        assert float(stats["dropped"]) == 0.0
        total = total + (part - shared)
        theirs = ref.sparse_ffn(model, Numerics(), x, mine, first=chip)
        assert float(jnp.max(jnp.abs(part - theirs))) <= 1e-5 * float(
            jnp.max(jnp.abs(theirs)))
    assert float(jnp.max(jnp.abs(total - whole))) <= 1e-5 * float(
        jnp.max(jnp.abs(whole)))


def test_an_unknown_score_function_is_refused():
    with pytest.raises(ValueError):
        RoutedExperts(E, K, F, (0, E), score="tanh").init(
            jax.random.key(0), jnp.zeros((4, D)))


# ---- the block loop: only blocks that hold a row run, in both passes --------

BLOCK = 40     # 2 of 8 experts held: 2 x 80 assignments x 2 / 8

LOADS = {
    # load: (every token's first choice, its second, rows held, blocks run)
    "no_row": (4, 5, 0, 0),
    "one_block_exactly": (0, 5, BLOCK, 1),
    "every_assignment": (0, 1, 2 * BLOCK, 2),
}


def loaded(load):
    """2 of 8 experts held: 80 assignments in two blocks of 40.  Every token
    chooses the same two experts, so the rows held are 0, 40 or 80."""
    m = layer(0, 2)
    x = jax.random.normal(jax.random.key(7), (T, D)).at[:, 0].set(1.0)
    params = jax.tree.map(lambda a: 10 * a,
                          m.init(jax.random.key(6), x)["params"])
    one, two, rows, blocks = LOADS[load]
    params["router"]["kernel"] = params["router"]["kernel"].at[0, one].add(
        12.0).at[0, two].add(6.0)
    return m, params, x, rows, blocks


@pytest.mark.parametrize("load", list(LOADS))
def test_the_loop_runs_the_blocks_that_hold_a_row_and_no_other(load):
    m, params, x, rows, blocks = loaded(load)
    out, stats = m.apply({"params": params}, x)
    assert float(stats["rows"]) == rows
    assert float(stats["blocks_run"]) == blocks == -(-rows // BLOCK)
    assert float(stats["dropped"]) == 0.0


@pytest.mark.parametrize("load", list(LOADS))
def test_values_and_gradients_match_a_dense_loop_at_every_load(load):
    m, params, x, rows, _ = loaded(load)
    out, _ = m.apply({"params": params}, x)
    ref = dense_loop(params, x, 0, 2)
    assert float(jnp.max(jnp.abs(out - ref))) <= 1e-5 * float(jnp.max(jnp.abs(ref)))
    f = lambda fn: lambda p, x: jnp.sum(jnp.sin(fn(p, x)))  # noqa: E731
    got = jax.grad(f(lambda p, x: m.apply({"params": p}, x)[0]), (0, 1))(params, x)
    want = jax.grad(f(lambda p, x: dense_loop(p, x, 0, 2)), (0, 1))(params, x)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-4 * float(
            jnp.max(jnp.abs(b))) + 1e-6, jax.tree_util.keystr(path)
    if rows == 0:       # the shared expert's alone: nothing reaches an expert
        assert float(jnp.max(jnp.abs(out - dense_loop(params, x, 0, 0)))) == 0.0
        assert all(float(jnp.max(jnp.abs(g))) == 0.0
                   for g in jax.tree.leaves(got[0]["experts"]))
    else:
        assert float(jnp.max(jnp.abs(got[0]["router"]["kernel"]))) > 0.0
        assert all(float(jnp.max(jnp.abs(g))) > 0.0
                   for g in jax.tree.leaves(got[0]["experts"]))


def test_it_differentiates_under_checkpoint_inside_a_scan_over_layers():
    """As ``hybrid.py`` and ``latent.py`` run it: rematerialised, scanned over
    stacked layers.  Two layers' gradients match the unscanned sum."""
    m, params, x, _, _ = loaded("every_assignment")
    other = jax.tree.map(lambda a: 0.5 * a, params)
    stacked = jax.tree.map(lambda a, b: jnp.stack([a, b]), params, other)

    def scanned(stack, x):
        layer_fn = jax.checkpoint(lambda p, h: m.apply({"params": p}, h))

        def step(h, p):
            y, stats = layer_fn(p, h)
            return h + 0.1 * jnp.tanh(y), stats["blocks_run"]

        h, runs = jax.lax.scan(step, x, stack)
        return jnp.sum(jnp.sin(h)), runs

    def unscanned(stack, x):
        h = x
        for i in range(2):
            y, _ = m.apply({"params": jax.tree.map(lambda a: a[i], stack)}, h)
            h = h + 0.1 * jnp.tanh(y)
        return jnp.sum(jnp.sin(h))

    (value, runs), got = jax.value_and_grad(scanned, (0, 1), has_aux=True)(stacked, x)
    want_value, want = jax.value_and_grad(unscanned, (0, 1))(stacked, x)
    assert runs.tolist() == [2.0, 2.0]
    assert float(value) == pytest.approx(float(want_value), rel=1e-5)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert float(jnp.max(jnp.abs(a - b))) <= 1e-4 * float(jnp.max(jnp.abs(b))) + 1e-6


def _loop_bodies(jaxpr):
    """Every jaxpr that runs once an iteration of some loop under ``jaxpr``
    (a ``while``'s or a ``scan``'s body), with all that is nested in it."""
    def subjaxprs(eqn):
        for v in eqn.params.values():
            for u in (v if isinstance(v, (tuple, list)) else (v,)):
                u = getattr(u, "jaxpr", u)
                if hasattr(u, "eqns"):
                    yield u

    def walk(j, inside):
        for eqn in j.eqns:
            for sub in subjaxprs(eqn):
                looped = inside or eqn.primitive.name in ("while", "scan")
                if looped:
                    yield sub
                yield from walk(sub, looped)

    return list(walk(jaxpr, False))


def test_no_block_of_either_pass_makes_a_zero_tensor_of_the_sums_shape():
    """What the scan over all blocks paid for a skipped one: a ``(t, d)``
    zero tensor in the forward pass, and in the backward pass one of the
    shape of everything its body read (tokens, the three expert stacks)."""
    m, params, x = make(0, 3)           # 80 assignments in blocks of 60
    f = lambda p, x: jnp.sum(jnp.sin(m.apply({"params": p}, x)[0]))  # noqa: E731
    jaxpr = jax.make_jaxpr(jax.value_and_grad(f, (0, 1)))(params, x).jaxpr
    sums = {(T, D), (3, D, F), (3, F, D)}
    bodies = _loop_bodies(jaxpr)
    assert len(bodies) >= 2          # the forward loop's and the backward's
    made = [tuple(eqn.outvars[0].aval.shape) for body in bodies
            for eqn in body.eqns if eqn.primitive.name == "broadcast_in_dim"]
    # a body masks its rows with zeros of a block's shape, and makes no other
    assert (60, D) in made and not [s for s in made if s in sums], made
