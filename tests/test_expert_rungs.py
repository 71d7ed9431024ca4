"""The expert layer's capacity rungs (``models/transformer.py``
``pair_rungs`` / ``TopKMoeMlp.routed``): the per-pair arrays are as long as
the smallest rung that holds the held pairs, chosen in the step from their
count, and the layer counts the rows it moved. Exactness against the layer
written with arrays of all N x k pairs (kept here as the reference), with
the routing planted so that the count lands on each rung, one past it, at
0 and at N x k, for the sigmoid router and the group-limited softmax one,
alone and inside ``lax.fori_loop`` as the prefill loop calls it; and the
one rung of a small call, through the way back's kernel and, for a shape
the kernel does not take, through XLA. CPU, float32: results and counts
only."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from video_edge_ai_proxy_tpu.models import deepseek_v2, lfm2, xing4
from video_edge_ai_proxy_tpu.models.transformer import (
    TopKMoeConfig, TopKMoeMlp, pair_rungs, topk_route)

N, E, K, DIM, WIDTH = 1024, 32, 4, 128, 16
HELD = (0, 1, 2, 3)              # group 0 of the group-limited router
ROUTERS = {
    "sigmoid": TopKMoeConfig(dim=DIM, mlp_dim=WIDTH, num_experts=E, top_k=K,
                             experts_held=HELD),
    "group_limited": TopKMoeConfig(
        dim=DIM, mlp_dim=WIDTH, num_experts=E, top_k=K, experts_held=HELD,
        use_expert_bias=False, norm_topk_prob=False,
        routed_scaling_factor=2.5, shared_mlp_dim=WIDTH, scoring="softmax",
        n_group=8, topk_group=2),
}
RUNGS = (4096, 2048, 1024)       # N x k, then halves down to 2 x N x k x 4/32


def _params(cfg, seed):
    """The gate an identity (a token's logits are its first E features),
    the experts drawn from the seed."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    h = len(cfg.held)
    p = {"gate": jnp.eye(DIM, E),
         "w1": jax.random.normal(ks[0], (h, DIM, WIDTH)) * 0.2,
         "w3": jax.random.normal(ks[1], (h, DIM, WIDTH)) * 0.2,
         "w2": jax.random.normal(ks[2], (h, WIDTH, DIM)) * 0.2}
    if cfg.use_expert_bias:
        p["expert_bias"] = jnp.zeros((E,))
    if cfg.shared_mlp_dim:
        p["shared_w1"] = jax.random.normal(ks[3], (DIM, WIDTH)) * 0.2
        p["shared_w3"] = jax.random.normal(ks[4], (DIM, WIDTH)) * 0.2
        p["shared_w2"] = jax.random.normal(ks[5], (WIDTH, DIM)) * 0.2
    return {"params": p}


def _planted(count, seed, n=N):
    """Tokens [n, DIM] whose chosen k experts put ``count`` pairs on the
    held ones: a token takes m of them and k - m of group 1 (experts
    4..7), so the group limit keeps what it chose."""
    rng = np.random.default_rng(seed)
    m = np.zeros(n, np.int64)
    m[:count // K] = K
    if count % K:
        m[count // K] = count % K
    rng.shuffle(m)
    x = 0.1 * rng.standard_normal((n, DIM))
    for i, mi in enumerate(m):
        chosen = np.concatenate([rng.permutation(HELD)[:mi],
                                 rng.permutation(np.arange(4, 8))[:K - mi]])
        x[i, chosen] += 4.0
    return jnp.asarray(x, jnp.float32)


def _full_path(variables, x, cfg):
    """The layer as it was written before its rungs: every array as long
    as all N x k routed pairs (y [N, d], load [held])."""
    p = variables["params"]
    n, d = x.shape
    k, h = cfg.top_k, len(cfg.held)
    logits = jnp.dot(x, p["gate"], precision=jax.lax.Precision.HIGHEST)
    scores = (jax.nn.softmax(logits, axis=-1) if cfg.scoring == "softmax"
              else jax.nn.sigmoid(logits))
    sel, w = topk_route(scores, p.get("expert_bias"), cfg)
    lut = np.full((cfg.num_experts,), h, np.int32)
    lut[list(cfg.held)] = np.arange(h, dtype=np.int32)
    key = jnp.asarray(lut)[sel].reshape(n * k)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(key[:, None] == jnp.arange(h, dtype=jnp.int32)[None],
                    axis=0, dtype=jnp.int32)
    here = jnp.arange(n * k) < jnp.sum(sizes)
    xs = jnp.take(x, order // k, axis=0)
    up = jax.lax.ragged_dot(xs, p["w3"], sizes)
    act = nn.silu(jax.lax.ragged_dot(xs, p["w1"], sizes)) * up
    ys = jax.lax.ragged_dot(act, p["w2"], sizes,
                            preferred_element_type=jnp.float32)
    ys = jnp.where(here[:, None], ys, 0.0)
    back = jnp.take(ys, jnp.argsort(order), axis=0).reshape(n, k, d)
    y = jnp.einsum("nkd,nk->nd", back, w)
    if cfg.shared_mlp_dim:
        y = y + (nn.silu(x @ p["shared_w1"]) * (x @ p["shared_w3"])) \
            @ p["shared_w2"]
    return y, sizes


def _close(got, want):
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_allclose(got, want, rtol=1e-6,
                               atol=1e-6 * float(np.abs(want).max()))


_CASES = [(r, c, False) for r in ROUTERS for c in
          (0, RUNGS[2], RUNGS[2] + 1, RUNGS[1], RUNGS[1] + 1, N * K)]
_CASES += [(r, (RUNGS[2], N * K), True) for r in ROUTERS]


def _case_id(router, count, loop):
    if loop:
        return f"{router}-loop-" + "-".join(map(str, count))
    return f"{router}-{count}"


@pytest.mark.parametrize("router,count,loop", _CASES,
                         ids=[_case_id(*c) for c in _CASES])
def test_a_rung_gives_what_all_pairs_give(router, count, loop):
    """The held-pair count at a rung, one over it (the next rung up), at 0
    and at N x k (every pair held: the top rung, dropless): ``y`` and
    ``load`` are the all-pairs layer's, and the rows moved are the rung
    that holds the count. ``loop``: two chunks in ``lax.fori_loop``, one
    on the smallest rung and one on the top, as the prefill loop calls the
    layer."""
    cfg = ROUTERS[router]
    assert pair_rungs(cfg, N) == RUNGS
    layer = TopKMoeMlp(cfg, dtype=jnp.float32)
    variables = _params(cfg, 7)
    if not loop:
        x = _planted(count, 3)
        y, counts = jax.jit(lambda v, x: layer.apply(
            v, x, method=TopKMoeMlp.routed))(variables, x)
        want, sizes = _full_path(variables, x, cfg)
        load = counts["held"]
        assert int(load.sum()) == count and int(counts["pairs"]) == N * K
        assert load.tolist() == sizes.tolist()
        _close(y, want)
        assert int(counts["rows"]) == min(r for r in RUNGS if r >= count)
        return
    xs = jnp.stack([_planted(c, 11 + i) for i, c in enumerate(count)])

    def chunks(variables, xs):
        def body(i, carry):
            ys, load, rows = carry
            y, n = layer.apply(variables, xs[i], method=TopKMoeMlp.routed)
            return ys.at[i].set(y), load + n["held"], rows + n["rows"]
        return jax.lax.fori_loop(0, len(count), body, (
            jnp.zeros_like(xs), jnp.zeros((len(HELD),), jnp.int32),
            jnp.zeros((), jnp.int32)))

    ys, load, rows = jax.jit(chunks)(variables, xs)
    total = np.zeros(len(HELD), np.int64)
    for i in range(len(count)):
        want, sizes = _full_path(variables, xs[i], cfg)
        _close(ys[i], want)
        total += np.asarray(sizes)
    assert load.tolist() == total.tolist() and int(load.sum()) == sum(count)
    assert int(rows) == RUNGS[2] + N * K


@pytest.mark.parametrize("router", sorted(ROUTERS))
@pytest.mark.parametrize("n", [64, 60], ids=["kernel", "xla"])
def test_one_rung_gives_what_all_pairs_give(router, n):
    """A call under a rung's few tiles (decode's) has the one rung of every
    pair: with ``n`` a multiple of 8 its way back is the kernel, else XLA's
    weighted sum; either gives the all-pairs layer's ``y`` and ``load``, and
    counts every pair as moved."""
    cfg = ROUTERS[router]
    assert pair_rungs(cfg, n) == (n * K,)
    layer = TopKMoeMlp(cfg, dtype=jnp.float32)
    variables = _params(cfg, 5)
    x = _planted(n * K // 3, 17, n)
    y, counts = jax.jit(lambda v, x: layer.apply(
        v, x, method=TopKMoeMlp.routed))(variables, x)
    want, sizes = _full_path(variables, x, cfg)
    assert counts["held"].tolist() == sizes.tolist()
    assert int(counts["rows"]) == int(counts["pairs"]) == n * K
    _close(y, want)


@pytest.mark.parametrize("cfg,n,rungs", [
    (deepseek_v2.DeepseekV2Config().moe, 4 * 784,
     (18816, 9472, 4736, 2432)),
    (lfm2.Lfm2Config().moe, 16 * 784, (50176, 25088)),
    (xing4.Xing4Config().moe, 8 * 784, (25088, 12544)),
    (deepseek_v2.DeepseekV2Config().moe, 64, (384,)),
    (xing4.Xing4Config().moe, 128, (512,)),
    (lfm2.Lfm2Config().moe, 64, (256,)),
    (TopKMoeConfig(dim=128, mlp_dim=8, num_experts=16, top_k=4), 4096,
     (16384,)),
    (ROUTERS["sigmoid"], N + 4, ((N + 4) * K,)),
    (TopKMoeConfig(dim=96, mlp_dim=8, num_experts=32, top_k=4,
                   experts_held=HELD), N, (N * K,)),
], ids=["dsv2-prefill", "lfm2-prefill", "xing4-prefill", "dsv2-decode",
        "xing4-decode", "lfm2-decode", "all-held", "tokens-not-by-8",
        "width-not-by-128"])
def test_the_ladder_comes_from_shapes_alone(cfg, n, rungs):
    """The cells' prefill chunks get a ladder down to the first rung at or
    above twice the even held share; decode's few tokens, a holder of every
    expert, and shapes the way back's kernel does not take get the one rung
    of all pairs (the layer as it was)."""
    assert pair_rungs(cfg, n) == rungs
    assert all(r % 128 == 0 for r in rungs[1:])


_WAY_BACK = {   # n, k, d, rows C, count, lanes of a slice (None: the rule's)
    "some-held": (64, 6, 256, 160, 150, None),
    "none-held": (48, 4, 256, 16, 0, None),
    "all-held": (32, 4, 256, 128, 128, None),
    "top-22": (64, 22, 256, 1408, 300, None),
    "three-slices": (40, 4, 384, 96, 70, 128),
    "count-inside-a-block": (16, 24, 2048, 384, 200, None),
    "one-token-several-rows": (24, 8, 128, 64, 40, None),
}


@pytest.mark.parametrize("case", sorted(_WAY_BACK))
def test_the_way_back_adds_only_the_rows_under_count(case, monkeypatch):
    """``ops/combine_rows.py`` (interpreted off the chip): each held row
    under ``count``, weighted, added to its token, in the order the rows
    lie; the rows past ``count`` hold NaN and are never added. The rows lie
    as the layer sorts them: a pair's token is its slot // k, the slots in
    a seeded order. Against a NumPy loop over the rows, at top-22, with the
    output in three slices of 128 lanes (the VMEM budget set to what one
    slice and its row blocks take), with ``count`` inside the second of
    three row blocks, and with every row of one token held."""
    from video_edge_ai_proxy_tpu.ops import combine_rows as way_back

    n, k, d, c, count, lanes = _WAY_BACK[case]
    if lanes:
        monkeypatch.setattr(way_back, "_VMEM_BUDGET", 4 * n * lanes
                            + way_back._blocks_bytes(lanes))
        way_back.combine_rows.clear_cache()
    lanes = lanes or d
    assert way_back.combine_slice(n, d) == lanes
    if case == "count-inside-a-block":
        assert way_back._block_rows(c, lanes) == 128 < count < 256 < c
    rng = np.random.default_rng(n + k)
    slots = rng.permutation(n * k)
    if case == "one-token-several-rows":
        mine = np.isin(slots, np.arange(5 * k, 6 * k))
        slots = np.concatenate([slots[mine], slots[~mine]])
        slots[:count] = rng.permutation(slots[:count])
    tok = (slots[:c] // k).astype(np.int32)
    rows = rng.standard_normal((c, d)).astype(np.float32)
    rows[count:] = np.nan
    w = rng.standard_normal(c).astype(np.float32)
    got = np.asarray(way_back.combine_rows(
        jnp.asarray(rows), jnp.asarray(tok), jnp.asarray(w), count, n))
    want = np.zeros((n, d), np.float32)
    for r in range(count):
        want[tok[r]] += w[r] * rows[r]
    if case == "one-token-several-rows":
        assert (tok[:count] == 5).sum() == k
    assert got.shape == (n, d) and np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
