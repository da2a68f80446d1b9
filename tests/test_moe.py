"""MoE / expert parallelism: dispatch parity + all_to_all EP vs single rank.

No reference analog (apex has no MoE — beyond-reference extension); the test
strategy mirrors the TP suites: sharded execution on the 8-device CPU mesh
must reproduce a single-device ground truth bit-for-bit up to dtype noise.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from apex_tpu.mesh import DATA_AXIS


def _dense_moe_reference(x, router_w, w1, b1, w2, b2, k, normalize):
    """Ground truth: every token through its top-k experts, no capacity."""
    logits = x.astype(np.float32) @ router_w.T
    probs = jax.nn.softmax(jnp.asarray(logits), axis=-1)
    top_vals, top_idx = jax.lax.top_k(probs, k)
    top_vals = np.asarray(top_vals)
    if normalize:
        top_vals = top_vals / top_vals.sum(-1, keepdims=True)
    out = np.zeros_like(x, dtype=np.float32)
    for t in range(x.shape[0]):
        for i in range(k):
            e = int(top_idx[t, i])
            h = np.asarray(jax.nn.gelu(jnp.asarray(
                x[t] @ w1[e] + b1[e])))
            out[t] += top_vals[t, i] * (h @ w2[e] + b2[e])
    return out


def _ample_capacity(num_experts, k):
    # capacity = cf * k * T / E >= T  <=>  cf >= E / k: dropless
    return float(num_experts) / k + 1.0


def test_single_rank_moe_matches_dense_reference(rng):
    from apex_tpu.transformer.moe import MoEMLP

    d, ff, e, k, t = 8, 16, 4, 2, 12
    layer = MoEMLP(hidden_size=d, ffn_hidden_size=ff, num_experts=e, k=k,
                   capacity_factor=_ample_capacity(e, k),
                   expert_world_size=1, axis_name="nope")
    x = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    v = layer.init(jax.random.PRNGKey(0), x)
    y, aux = layer.apply(v, x)

    p = v["params"]
    ref = _dense_moe_reference(
        np.asarray(x), np.asarray(p["router"]["weight"]),
        np.asarray(p["w1"]), np.asarray(p["b1"]),
        np.asarray(p["w2"]), np.asarray(p["b2"]), k, True)
    np.testing.assert_allclose(np.asarray(y), ref, rtol=2e-4, atol=2e-4)
    assert float(aux.load_balance) >= 1.0 - 1e-5  # lower bound at uniform
    assert np.isfinite(float(aux.z_loss))


def test_capacity_drops_tokens(rng):
    """With capacity 1 slot/expert most assignments drop; output shrinks."""
    from apex_tpu.transformer.moe import MoEMLP

    d, ff, e, t = 8, 16, 4, 32
    x = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    ample = MoEMLP(hidden_size=d, ffn_hidden_size=ff, num_experts=e, k=1,
                   capacity_factor=_ample_capacity(e, 1),
                   expert_world_size=1, axis_name="nope")
    v = ample.init(jax.random.PRNGKey(0), x)
    y_full, _ = ample.apply(v, x)
    tight = MoEMLP(hidden_size=d, ffn_hidden_size=ff, num_experts=e, k=1,
                   capacity_factor=e / t,  # 1 slot per expert
                   expert_world_size=1, axis_name="nope")
    y_tight, _ = tight.apply(v, x)
    dropped = np.sum(np.all(np.asarray(y_tight) == 0.0, axis=-1))
    assert dropped >= t - 2 * e  # at most 2*... only e slots survive...
    assert not np.allclose(np.asarray(y_full), np.asarray(y_tight))


@pytest.mark.slow
@pytest.mark.parametrize("ep", [2, 4])
def test_expert_parallel_matches_single_rank(rng, ep):
    """ep-way all_to_all MoE == single-rank MoE with the same stacked params."""
    from apex_tpu.transformer.moe import MoEMLP

    d, ff, e, k = 8, 16, 8, 2
    t_per = 8                      # tokens per rank
    t = t_per * ep
    cf = _ample_capacity(e, k)

    x = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)

    # ground truth on one rank, full expert stack
    single = MoEMLP(hidden_size=d, ffn_hidden_size=ff, num_experts=e, k=k,
                    capacity_factor=cf, expert_world_size=1, axis_name="nope")
    v = single.init(jax.random.PRNGKey(1), x)
    y_ref, aux_ref = single.apply(v, x)

    # shard the same params: rank r owns experts [r*e/ep, (r+1)*e/ep)
    p = v["params"]
    e_loc = e // ep
    sharded_params = {
        "router": {"weight": p["router"]["weight"]},   # replicated
        "w1": p["w1"].reshape(ep, e_loc, d, ff),
        "b1": p["b1"].reshape(ep, e_loc, ff),
        "w2": p["w2"].reshape(ep, e_loc, ff, d),
        "b2": p["b2"].reshape(ep, e_loc, d),
    }
    par = MoEMLP(hidden_size=d, ffn_hidden_size=ff, num_experts=e, k=k,
                 capacity_factor=cf, expert_world_size=ep,
                 axis_name=DATA_AXIS)

    # an ep-sized mesh so the data axis IS the expert-parallel group
    devs = jax.devices()[:ep]
    from jax.sharding import Mesh
    small = Mesh(np.asarray(devs).reshape(ep, 1, 1, 1),
                 ("data", "stage", "context", "model"))

    @functools.partial(
        jax.shard_map, mesh=small,
        in_specs=(P("data"), P("data"), P()), out_specs=(P("data"), P()),
        check_vma=False)
    def run(xx, wstack, rw):
        variables = {"params": {
            "router": {"weight": rw},
            "w1": wstack["w1"][0], "b1": wstack["b1"][0],
            "w2": wstack["w2"][0], "b2": wstack["b2"][0]}}
        y, aux = par.apply(variables, xx)
        return y, aux.load_balance

    wstack = {kk: sharded_params[kk] for kk in ("w1", "b1", "w2", "b2")}
    y_par, lb_par = run(x, wstack, p["router"]["weight"])

    np.testing.assert_allclose(np.asarray(y_par), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)


def test_moe_grads_flow_and_balance_loss_differentiable(rng):
    from apex_tpu.transformer.moe import MoEMLP

    d, ff, e, k, t = 8, 16, 4, 2, 16
    layer = MoEMLP(hidden_size=d, ffn_hidden_size=ff, num_experts=e, k=k,
                   capacity_factor=_ample_capacity(e, k),
                   expert_world_size=1, axis_name="nope",
                   aux_loss_coeff=1e-2, z_loss_coeff=1e-3)
    x = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    v = layer.init(jax.random.PRNGKey(0), x)

    def loss(params, xx):
        y, aux = layer.apply({"params": params}, xx)
        return jnp.sum(y * y) + aux.total

    g = jax.grad(loss)(v["params"], x)
    flat = jax.tree_util.tree_leaves(g)
    assert all(np.all(np.isfinite(np.asarray(l))) for l in flat)
    # router weight must receive gradient (through gates AND aux losses)
    assert float(jnp.sum(jnp.abs(g["router"]["weight"]))) > 0.0
    # every expert weight tensor must receive gradient
    assert float(jnp.sum(jnp.abs(g["w1"]))) > 0.0


def test_swiglu_experts_match_manual(rng):
    """activation='swiglu' experts: dropless MoE output == manual top-k
    routing through silu(x@gate)*(x@up) @ down per expert."""
    from apex_tpu.transformer.moe import MoEMLP

    d, ff, e, k, t = 8, 16, 4, 2, 12
    layer = MoEMLP(hidden_size=d, ffn_hidden_size=ff, num_experts=e, k=k,
                   capacity_factor=_ample_capacity(e, k),
                   activation="swiglu", expert_world_size=1,
                   axis_name="nope")
    x = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    v = layer.init(jax.random.PRNGKey(0), x)
    y, _ = layer.apply(v, x)

    p = v["params"]
    assert "b1" not in p and "b2" not in p  # bias-free like Mixtral w1/w3/w2
    logits = np.asarray(x, np.float32) @ np.asarray(
        p["router"]["weight"]).T
    probs = np.asarray(jax.nn.softmax(jnp.asarray(logits), -1))
    top_idx = np.argsort(-probs, axis=-1)[:, :k]
    out = np.zeros((t, d), np.float32)
    w1 = np.asarray(p["w1"])
    w2 = np.asarray(p["w2"])
    for ti in range(t):
        gates = probs[ti, top_idx[ti]]
        gates = gates / gates.sum()
        for gi, ei in zip(gates, top_idx[ti]):
            hh = np.asarray(x[ti]) @ w1[ei]
            gate_h, up_h = hh[:ff], hh[ff:]
            act = np.asarray(jax.nn.silu(jnp.asarray(gate_h))) * up_h
            out[ti] += gi * (act @ w2[ei])
    np.testing.assert_allclose(np.asarray(y), out, rtol=2e-4, atol=2e-4)


def test_moe_under_gspmd_jit_sharded_experts(rng):
    """Dense-dispatch MoEMLP under plain jit with the expert stacks sharded
    over ``data`` via NamedSharding: GSPMD partitions the dispatch/expert
    einsums itself (inserting the all_to_alls), and the result must match
    the unsharded single-device module — the pjit-trainer consumption path
    (no shard_map)."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from apex_tpu.transformer.moe import MoEMLP

    d, ff, e, k, t = 8, 16, 8, 2, 32
    layer = MoEMLP(hidden_size=d, ffn_hidden_size=ff, num_experts=e, k=k,
                   capacity_factor=_ample_capacity(e, k),
                   expert_world_size=1, axis_name="nope")
    x = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    v = layer.init(jax.random.PRNGKey(0), x)
    y_ref, _ = layer.apply(v, x)

    mesh = Mesh(np.asarray(jax.devices()[:4]), ("data",))
    exp_sh = NamedSharding(mesh, P("data"))      # experts split over data
    rep_sh = NamedSharding(mesh, P())
    p = v["params"]
    p_sharded = {
        "router": jax.device_put(p["router"], rep_sh),
        "w1": jax.device_put(p["w1"], exp_sh),
        "b1": jax.device_put(p["b1"], exp_sh),
        "w2": jax.device_put(p["w2"], exp_sh),
        "b2": jax.device_put(p["b2"], exp_sh),
    }
    x_sh = jax.device_put(x, NamedSharding(mesh, P("data")))

    @jax.jit
    def f(params, xx):
        y, aux = layer.apply({"params": params}, xx)
        return y, aux.total

    with mesh:
        y, aux = f(p_sharded, x_sh)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)
    assert np.isfinite(float(aux))


@pytest.mark.slow
@pytest.mark.parametrize("activation", ["gelu", "swiglu"])
def test_expert_tensor_parallel_matches_single_rank(rng, activation):
    """EP x expert-TP: (data=2, model=2) mesh — experts split over data AND
    their FFN dim over model (w2 partials psum'd) == the single-rank
    full-expert module."""
    from apex_tpu.transformer.moe import MoEMLP

    d, ff, e, k = 8, 16, 4, 2
    ep, tp = 2, 2
    t_per = 8
    t = t_per * ep
    cf = _ample_capacity(e, k)
    ffl = ff // tp
    e_loc = e // ep

    x = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    single = MoEMLP(hidden_size=d, ffn_hidden_size=ff, num_experts=e, k=k,
                    capacity_factor=cf, activation=activation,
                    expert_world_size=1, axis_name="nope")
    v = single.init(jax.random.PRNGKey(2), x)
    y_ref, _ = single.apply(v, x)
    p = v["params"]

    # slice: expert rows over ep; FFN cols over tp ([gate_r|up_r] for swiglu)
    def w1_slice(er, tr):
        w = np.asarray(p["w1"])[er * e_loc:(er + 1) * e_loc]
        if activation == "swiglu":
            gate, up = w[..., :ff], w[..., ff:]
            return np.concatenate([gate[..., tr * ffl:(tr + 1) * ffl],
                                   up[..., tr * ffl:(tr + 1) * ffl]], -1)
        return w[..., tr * ffl:(tr + 1) * ffl]

    def w2_slice(er, tr):
        return np.asarray(p["w2"])[er * e_loc:(er + 1) * e_loc,
                                   tr * ffl:(tr + 1) * ffl]

    stacked = {
        "w1": np.stack([[w1_slice(er, tr) for tr in range(tp)]
                        for er in range(ep)]),
        "w2": np.stack([[w2_slice(er, tr) for tr in range(tp)]
                        for er in range(ep)]),
    }
    if activation == "gelu":
        b1 = np.asarray(p["b1"])
        stacked["b1"] = np.stack(
            [[b1[er * e_loc:(er + 1) * e_loc, tr * ffl:(tr + 1) * ffl]
              for tr in range(tp)] for er in range(ep)])
        # b2 replicated over tp (added after the psum)
        stacked["b2"] = np.stack(
            [[np.asarray(p["b2"])[er * e_loc:(er + 1) * e_loc]
              for _ in range(tp)] for er in range(ep)])

    par = MoEMLP(hidden_size=d, ffn_hidden_size=ff, num_experts=e, k=k,
                 capacity_factor=cf, activation=activation,
                 expert_world_size=ep, axis_name="data",
                 tensor_world_size=tp, tensor_parallel_axis="model")

    from jax.sharding import Mesh
    devs = jax.devices()[:ep * tp]
    mesh = Mesh(np.asarray(devs).reshape(ep, 1, 1, tp),
                ("data", "stage", "context", "model"))

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P("data"), P("data", "model"), P()),
        out_specs=P("data"), check_vma=False)
    def run(xx, ws, rw):
        variables = {"params": dict(
            {"router": {"weight": rw}},
            **{kk: ws[kk][0, 0] for kk in ws})}
        y, _ = par.apply(variables, xx)
        return y

    ws = {kk: jnp.asarray(vv) for kk, vv in stacked.items()}
    y_par = run(x, ws, p["router"]["weight"])
    np.testing.assert_allclose(np.asarray(y_par), np.asarray(y_ref),
                               rtol=2e-4, atol=2e-4)


# -- the routed experts' served layout (transformer/moe/dropless.py) ------------

@pytest.mark.parametrize("d,m,w", [
    (2304, 896, 1024),   # Mellum: 64 KB tiles at 896, 256 KB at 1024
    (2048, 1536, 1536),  # GLM-4.7-Flash: tiles at 512 already
    (2048, 512, 512),    # Qwen3-Next
    (2048, 768, 768),    # the padding would cost more bytes than it saves
])
def test_served_width_is_read_off_the_shapes(d, m, w):
    from apex_tpu.transformer.moe.dropless import served_width

    assert served_width(d, m) == w


@pytest.mark.parametrize("d,m,itemsize,w", [
    (64, 48, 4, 128),    # the tiny Mellum and GLM configs: 3 grid steps
    (64, 32, 4, 32),     # the tiny Qwen3-Next: one step a product already
])
def test_served_width_at_the_tiny_configs(d, m, itemsize, w):
    from apex_tpu.transformer.moe.dropless import served_width

    assert served_width(d, m, itemsize) == w


def _padded(gate, up, down, w):
    m = gate.shape[2]
    return (jnp.pad(gate, ((0, 0), (0, 0), (0, w - m))),
            jnp.pad(up, ((0, 0), (0, 0), (0, w - m))),
            jnp.pad(down, ((0, 0), (0, w - m), (0, 0))))


@pytest.mark.parametrize("e,d,m,w,k,first", [
    (8, 64, 48, 128, 2, None),     # Mellum-like: 48 -> 128
    (8, 64, 96, 128, 4, None),     # GLM-like, top 4
    (16, 64, 48, 128, 4, 4),       # a share: experts 4..19 of 24 held,
                                   # rows past the last group
])
def test_grouped_experts_on_zero_padded_stacks_equal_the_published(
        rng, e, d, m, w, k, first):
    from apex_tpu.transformer.moe import grouped_experts

    t = 24
    x = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, e + (8 if first else 0), (t, k)),
                      jnp.int32)
    weights = jnp.asarray(rng.random((t, k)), jnp.float32)
    gate, up = (jnp.asarray(rng.standard_normal((e, d, m)) * 0.1,
                            jnp.float32) for _ in range(2))
    down = jnp.asarray(rng.standard_normal((e, m, d)) * 0.1, jnp.float32)
    want, sizes = grouped_experts(x, idx, weights, gate, up, down,
                                  first=first)
    got, got_sizes = grouped_experts(x, idx, weights,
                                     *_padded(gate, up, down, w),
                                     first=first)
    if first is not None:
        assert int(sizes.sum()) < t * k     # some pairs are elsewhere
    np.testing.assert_array_equal(np.asarray(got_sizes), np.asarray(sizes))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


#: the rows a layer's products route in each routed cell's decode step and
#: admit programs (tokens x k) and the stack they meet: (experts, d, w) and
#: the router's experts where the stack is a share of them
_GLM = (64, 2048, 1536, None)     # and lfm2-24b-a2b's
_MELLUM = (64, 2304, 1024, None)  # served at 1024
_QWEN = (128, 2048, 512, 512)     # 128 of 512 held: a quarter of the rows


@pytest.mark.parametrize("rows,stack,served", [
    (32 * 4, _GLM, 128),          # glm-4.7-flash decode: tiles 128 already
    (48 * 8, _MELLUM, 384),       # mellum2 decode: 128
    (64 * 10, _QWEN, 640),        # qwen3-next decode: 128
    (128 * 4, _GLM, 640),         # lfm2 decode: 512 -> 128 high
    (65 * 8, _MELLUM, 640),       # 65 slots: an 8-row tile -> 128
    (65 * 4, _GLM, 384),          # 65 slots of top 4: a 4-row tile -> 128
    (256 * 4, _GLM, 1152),        # lfm2 admit 256 .. 4096 tokens: 512 ->
    (512 * 4, _GLM, 2176),        # 128 up to ~4k rows, 256 up to ~16k
    (1024 * 4, _GLM, 4224),       # (and glm admit 1k .. 4k tokens)
    (2048 * 4, _GLM, 8448),
    (4096 * 4, _GLM, 16640),
    (8192 * 4, _GLM, 32768),      # glm admit 8k, 16k tokens: 512, kept
    (16384 * 4, _GLM, 65536),
    (512 * 8, _MELLUM, 4224),     # mellum2 admit 512 .. 16k tokens
    (4096 * 8, _MELLUM, 32768),
    (8192 * 8, _MELLUM, 65536),
    (16384 * 8, _MELLUM, 131072),
    (1024 * 10, _QWEN, 10368),    # qwen3-next admit 1k .. 16k tokens,
    (4096 * 10, _QWEN, 41088),    # priced on the held quarter
    (8192 * 10, _QWEN, 82176),
    (16384 * 10, _QWEN, 164096),
    (8 * 4, _GLM, 32),            # 8 slots: a tile under the MXU's 128 rows
    (8, (8, 64, 128, None), 8),   # that no padding makes fuller
])
def test_served_rows_at_the_routed_cells(rows, stack, served):
    from apex_tpu.transformer.moe.dropless import (_grouped, _tile,
                                                   served_rows)

    e, d, w, router = stack
    got = served_rows(rows, e, d, w, grouped=_grouped(rows, e, router))
    assert got == served
    # the count, or an odd multiple of a row tile of 128 or more
    assert got == rows or (_tile(got) >= 128 and (got // _tile(got)) % 2)


@pytest.mark.parametrize("case", ["plain", "idle", "share"])
def test_padded_rows_change_no_product_and_no_stat(rng, monkeypatch, case):
    """The products handed zero rows in no group (an odd multiple of 128
    past the routed rows) give what the unpadded ones give, bit for bit,
    and the layer sows the same routing stats: the padding is no pair."""
    from apex_tpu.transformer.moe import dropless

    e, d, m, k, t = 8, 64, 48, 2, 24
    first = 4 if case == "share" else None
    x = np.asarray(rng.standard_normal((t, d)), np.float32)
    idx = rng.integers(0, e + (8 if first else 0), (t, k))
    if case == "idle":
        x[16:] = 0.0                  # idle slots route too
        idx[:] = idx % 5              # experts 5 .. 7 get no row
    x, idx = jnp.asarray(x), jnp.asarray(idx, jnp.int32)
    weights = jnp.asarray(rng.random((t, k)), jnp.float32)
    gate, up = (jnp.asarray(rng.standard_normal((e, d, m)) * 0.1,
                            jnp.float32) for _ in range(2))
    down = jnp.asarray(rng.standard_normal((e, m, d)) * 0.1, jnp.float32)
    layer = dropless.DroplessMoEMLP(
        hidden_size=d, ffn_hidden_size=m, num_experts=e + (8 if first else 0),
        k=k, held=e if first else None, first=first or 0)
    variables = layer.init(jax.random.PRNGKey(0), x)
    variables["params"]["experts"] = {"gate_proj": gate, "up_proj": up,
                                      "down_proj": down}

    def run(pad):
        monkeypatch.setattr(dropless, "served_rows",
                            lambda rows, *a, **kw: (-(-rows // 128) | 1)
                            * 128 if pad else rows)
        y, sizes = dropless.grouped_experts(
            x, idx, weights, gate, up, down, first=first,
            num_experts=e + 8 if first else None)
        _, sown = layer.apply(variables, x, mutable=["routing"])
        return y, sizes, sown["routing"]["stats"]

    want, got = run(False), run(True)
    assert t * k < 128
    if case == "idle":
        assert int((want[1] == 0).sum()) == 3
    if case == "share":
        assert int(want[1].sum()) < t * k
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_layout_moves_only_the_stacks_it_widens():
    """``lay_out_experts`` in place: a stack served wider leaves ``params``
    for ``served_experts`` zero-padded, one served as published stays; a
    second call finds nothing to lay out and reports the served width."""
    from apex_tpu.transformer.moe.dropless import (SERVED_COLLECTION,
                                                   lay_out_experts)

    def stack(d, m):
        return {"gate_proj": jnp.ones((2, d, m)),
                "up_proj": jnp.ones((2, d, m)),
                "down_proj": jnp.ones((2, m, d))}

    variables = {"params": {"a": {"experts": stack(64, 48),
                                  "router": {"weight": jnp.ones((2, 64))}},
                            "b": {"experts": stack(64, 32)}}}
    widths = lay_out_experts(variables)
    assert widths == {
        "a/experts": {"published": 48, "served": 128,
                      "bytes_added": 3 * 2 * 64 * 80 * 4},
        "b/experts": {"published": 32, "served": 32, "bytes_added": 0}}
    assert set(variables["params"]["a"]) == {"router"}
    assert variables["params"]["b"]["experts"]["gate_proj"].shape == (
        2, 64, 32)
    served = variables[SERVED_COLLECTION]["a"]["experts"]
    assert served["gate_proj"].shape == served["up_proj"].shape == (
        2, 64, 128)
    assert served["down_proj"].shape == (2, 128, 64)
    assert float(served["gate_proj"][:, :, 48:].sum()) == 0.0
    assert float(served["down_proj"][:, 48:].sum()) == 0.0
    again = lay_out_experts(variables)
    assert again["a/experts"] == {"published": None, "served": 128,
                                  "bytes_added": None}
    assert lay_out_experts(None) == {}
