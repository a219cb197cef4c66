"""The expert layer that holds a SHARE of the experts works on the sorted
pairs that are here, a block at a time (``DroplessMoE._pairs_here``):
values and every gradient against a loop over tokens and their chosen
experts written here in plain float32, at the routings that decide
whether a block runs; the layer that holds every expert still lowers to
the text it had; and the two counters that say how far the mechanism
engages against the routing the test constructed. Tiny sizes, CPU, a
block of 8 rows in the constant's place."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_pytorch_tpu import models
from distributed_pytorch_tpu.ops.losses import lm_mtp_loss
from distributed_pytorch_tpu.parallel import moe
from distributed_pytorch_tpu.parallel.moe import DroplessMoE, grouped_matmul
from distributed_pytorch_tpu.serve.pages import PagedSlotPool

BLOCK = 8
D, F, E, K, SCALE = 16, 8, 8, 2, 2.0
HELD = (2, 4)                      # experts 2..5 of 8 are here


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    monkeypatch.setattr(moe, "_BLOCK_ROWS", BLOCK)


class Chosen(DroplessMoE):
    """The layer with the experts of each token CHOSEN by the test
    (``chosen`` (T, k)); the weights are the router's own, from its
    scores, so its gradient is the layer's."""

    chosen = None

    def route(self, params, xt):
        g = jax.nn.sigmoid(xt @ params["router"]["w"])
        top_g = jnp.take_along_axis(g, self.chosen, axis=-1)
        return (self.chosen,
                top_g / (jnp.sum(top_g, -1, keepdims=True) + 1e-20)
                * self.scale, g)


def loop_reference(params, x, chosen, row_mask=None):
    """Token by token, pair by pair: what the held experts add to each
    token, nothing sorted, gathered or scattered."""
    first, count = HELD
    g = jax.nn.sigmoid(x @ params["router"]["w"])
    e = params["experts"]
    out = []
    for t in range(x.shape[0]):
        y = jnp.zeros((D,), jnp.float32)
        top_g = g[t, chosen[t]]
        w = top_g / (jnp.sum(top_g) + 1e-20) * SCALE
        for j, expert in enumerate(chosen[t]):
            if not first <= expert < first + count or (
                    row_mask is not None and not row_mask[t]):
                continue
            i = expert - first
            h = jax.nn.silu(x[t] @ e["gate"][i]) * (x[t] @ e["up"][i])
            y = y + w[j] * (h @ e["down"][i])
        out.append(y)
    return jnp.stack(out)


def pairs_of(per_token):
    """(T, 2) chosen experts from, a token, how many of its two pairs
    are here: held experts 2.., experts elsewhere 0, 1, 6, 7."""
    here, away = [2, 3, 4, 5], [0, 1, 6, 7]
    rows = []
    for t, n in enumerate(per_token):
        picks = [here[(t + j) % 4] for j in range(n)] \
            + [away[(t + j) % 4] for j in range(K - n)]
        rows.append(picks[::-1] if t % 2 else picks)
    return np.asarray(rows, np.int32)


#: name -> (pairs here a token, or None for the layer's own router;
#: rows a row_mask leaves out)
CASES = {
    "uniform-router": (None, ()),
    "no-pair-here": ([0] * 12, ()),
    "every-pair-here": ([2] * 12, ()),
    "count-on-a-block-edge": ([1] * 8 + [0] * 4, ()),
    "count-one-past-an-edge": ([1] * 7 + [2] + [0] * 4, ()),
    "row-mask": ([2, 1, 0, 1, 2, 1, 0, 1, 2, 1, 0, 1], (0, 5, 11)),
    "rows-no-multiple-of-a-block": ([1, 2, 0, 1, 2, 0, 1, 2, 0, 1, 2, 0, 1],
                                    ()),
    "one-block": ([2, 0, 1], ()),
}


@pytest.mark.parametrize("case", CASES)
def test_a_share_agrees_with_a_loop_over_tokens_and_chosen_experts(case):
    per_token, left_out = CASES[case]
    t = 12 if per_token is None else len(per_token)
    layer = (DroplessMoE if per_token is None else Chosen)(
        D, E, F, top_k=K, n_shared=0, scale=SCALE, held=HELD)
    params = layer.init(jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (t, D))
    row_mask = None
    if left_out:
        row_mask = np.ones((t,), bool)
        row_mask[list(left_out)] = False
    if per_token is None:
        chosen = np.asarray(layer.route(params, x)[0])
    else:
        chosen = layer.chosen = pairs_of(per_token)
    sent = np.ones((t,), bool) if row_mask is None else row_mask
    here = ((chosen >= HELD[0]) & (chosen < sum(HELD)) & sent[:, None])
    if per_token is not None:
        assert here.sum(1).tolist() == [n * s for n, s in zip(per_token, sent)]
    mask = None if row_mask is None else jnp.asarray(row_mask)

    y, counts, load = jax.jit(layer.routed)(params, x, mask)
    want = loop_reference(params, x, chosen, row_mask)
    np.testing.assert_allclose(y, want, atol=2e-6, rtol=1e-5)
    assert int(counts[0]) == here.sum()
    assert np.asarray(load).tolist() == np.bincount(
        chosen[sent].reshape(-1), minlength=E).tolist()
    run, blocks = layer.dispatch_blocks(t * K, int(counts[0]))
    assert blocks == -(-t * K // BLOCK)
    assert int(run) == (1 if blocks == 1 else -(-int(here.sum()) // BLOCK))

    # every pair dropped nowhere and every gradient, as a training step
    # takes them: jax.checkpoint around the layer, jax.grad outside
    cot = jnp.cos(jnp.arange(t * D, dtype=jnp.float32)).reshape(t, D)
    got = jax.jit(jax.grad(jax.checkpoint(
        lambda p, x: jnp.sum(layer.routed(p, x, mask)[0] * cot)),
        argnums=(0, 1)))(params, x)
    ref = jax.grad(lambda p, x: jnp.sum(
        loop_reference(p, x, chosen, row_mask) * cot), argnums=(0, 1))(
        params, x)
    flat, _ = jax.tree_util.tree_flatten_with_path(got)
    for (path, a), b in zip(flat, jax.tree_util.tree_leaves(ref)):
        np.testing.assert_allclose(a, b, atol=5e-6, rtol=1e-4,
                                   err_msg=jax.tree_util.keystr(path))
    if per_token is not None and sum(per_token):
        assert float(jnp.abs(got[0]["router"]["w"]).max()) > 0


def routed(self, params, xt, row_mask=None):
    """``DroplessMoE.routed`` as it stood before the layer told a share
    from the whole (PR 43), kept as the text a layer that holds every
    expert must still lower to."""
    t, k, c = xt.shape[0], self.top_k, self.count
    top_i, w, _ = self.route(params, xt)
    with jax.named_scope("route"):
        sent = jnp.ones((t, k), jnp.int32) if row_mask is None \
            else jnp.broadcast_to(row_mask[:, None], (t, k)).astype(
                jnp.int32)
        load = jnp.zeros((self.n_routed,), jnp.int32).at[
            top_i.reshape(-1)].add(sent.reshape(-1))
    with jax.named_scope("dispatch"):
        eid = top_i.reshape(-1) - self.first
        here = (eid >= 0) & (eid < c)
        if row_mask is not None:
            here &= jnp.repeat(row_mask, k)
        key = jnp.where(here, eid, c)
        order = jnp.argsort(key)
        sizes = jnp.sum(key[:, None] == jnp.arange(c)[None, :], axis=0,
                        dtype=jnp.int32)
        xs = jnp.take(xt, order // k, axis=0)
    with jax.named_scope("experts"):
        e = params["experts"]
        dot = lambda a, b: grouped_matmul(a, b, sizes)
        h = jax.nn.silu(dot(xs, e["gate"])) * dot(xs, e["up"])
        ys = dot(h.astype(xt.dtype), e["down"])
    with jax.named_scope("combine"):
        ys = jnp.where((jnp.arange(t * k) < jnp.sum(sizes))[:, None],
                       ys, 0.0)
        back = jnp.zeros((t * k,), jnp.int32).at[order].set(
            jnp.arange(t * k, dtype=jnp.int32))
        pairs = jnp.take(ys, back, axis=0).reshape(t, k, self.dim)
        y = jnp.sum(pairs * jnp.where(here.reshape(t, k), w, 0.0)[..., None],
                    axis=1)
    counts = jnp.stack([jnp.sum(sizes), jnp.sum(sizes > 0),
                        jnp.max(sizes)]).astype(jnp.int32)
    return y, counts, load


@pytest.mark.parametrize("score", ["sigmoid", "softmax"])
@pytest.mark.parametrize("masked", [False, True], ids=["all-rows", "row-mask"])
@pytest.mark.parametrize("grad", [False, True], ids=["forward", "gradient"])
def test_a_layer_that_holds_every_expert_lowers_to_the_text_it_had(
        score, masked, grad):
    """24 pairs are three blocks of 8 here: a layer that holds every
    expert must not have noticed."""
    layer = DroplessMoE(D, E, F, top_k=K, n_shared=0, scale=SCALE,
                        score=score, dtype=jnp.bfloat16)
    params = layer.init(jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (12, D), jnp.bfloat16)
    mask = jnp.arange(12) % 5 != 0 if masked else None

    def text(body):
        def routed_(p, x, mask):           # one name in both texts
            return body(p, x, mask)

        def with_grad(p, x, mask):
            return jax.grad(lambda p, x: jnp.sum(
                routed_(p, x, mask)[0]), argnums=(0, 1))(p, x)

        return jax.jit(with_grad if grad else routed_).lower(
            params, x, mask).as_text()

    now = text(layer.routed)
    assert now == text(lambda p, x, m: routed(layer, p, x, m))
    assert "while" not in now


def test_a_share_runs_its_blocks_in_a_loop_and_one_block_without():
    share = DroplessMoE(D, E, F, top_k=K, n_shared=0, held=HELD)
    params = share.init(jax.random.PRNGKey(3))
    lower = lambda t: jax.jit(share.routed).lower(
        params, jnp.zeros((t, D))).as_text()
    assert "while" in lower(12)            # 24 pairs: three blocks
    text = lower(4)                        # 8 pairs: the one block
    assert "while" not in text and "stablehlo.case" not in text \
        and "stablehlo.if" not in text


MODEL = dict(vocab=97, dim=32, n_layers=3, n_heads=4, max_seq=32, pos="none",
             block_kinds=("dense", "moe", "moe"), norm="rms", norm_eps=1e-6,
             ffn_dim=48,
             moe=dict(n_routed=8, width=16, top_k=2, n_shared=1, scale=2.0))


@pytest.mark.parametrize("held", [HELD, None], ids=["share", "every-expert"])
def test_router_metrics_count_the_blocks_the_routing_fills(held):
    model = models.TransformerLM(**{**MODEL, "moe": dict(MODEL["moe"],
                                                         held=held)}, mtp=1)
    params = model.init(jax.random.PRNGKey(5))
    tokens = jax.random.randint(jax.random.PRNGKey(6), (2, 15), 0, 97)
    _, aux = lm_mtp_loss(model, params, tokens, weight=0.3)
    load = np.asarray(aux["moe_load"])             # (3 layers, 8 experts)
    pairs = 2 * 14 * 2                             # a main layer's
    assert load.shape == (3, 8) and load[:2].sum(1).tolist() == [pairs] * 2
    blocks = sum(-(-int(n) // BLOCK) for n in load.sum(1))
    first, count = held or (0, 8)
    run = sum(-(-int(n) // BLOCK) for n in load[:, first:first + count].sum(1))
    assert float(aux["moe_dispatch_blocks"]) == blocks >= 3 * 6
    assert float(aux["moe_dispatch_blocks_run"]) == run
    assert (run < blocks) == (held is not None)
    assert float(aux["moe_pairs_here"]) == load[:, first:first + count].sum()


@pytest.mark.parametrize("held,slots", [(HELD, 3), (None, 3), (HELD, 6)],
                         ids=["share", "every-expert", "share-two-blocks"])
def test_pool_counts_the_decode_programs_blocks(held, slots):
    model = models.TransformerLM(**{**MODEL, "moe": dict(MODEL["moe"],
                                                         held=held)})
    params = model.init(jax.random.PRNGKey(5))
    pool = PagedSlotPool(model, slots, 32, page_len=4, n_pages=24)
    pool.admit(params, np.arange(6, dtype=np.int32), 0, (8,))
    active = np.zeros((slots,), bool)
    active[0] = True
    for step in range(3):
        pool.ensure_decode_capacity(0)
        pool.decode(params, np.full((slots,), 7 + step, np.int32), active)
    st = pool.moe_stats()
    # a pass routes slots x 2 pairs a layer: one block up to 4 slots
    calls = st["moe_decode_steps"] * st["moe_layers"]
    assert calls == 3 * 2
    per_call = -(-slots * 2 // BLOCK)
    assert st["moe_dispatch_blocks"] == calls * per_call
    if held is None or per_call == 1:
        assert st["moe_dispatch_blocks_run"] == st["moe_dispatch_blocks"]
    else:
        # one active row sends at most 2 pairs here a call: its first
        # block, or none; the pool knows the pairs' sum, so the fewest
        # blocks they can lie in
        assert st["moe_dispatch_blocks_run"] == -(
            -st["moe_tokens_routed"] // BLOCK) <= calls
