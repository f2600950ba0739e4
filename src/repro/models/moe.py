"""Mixture-of-Experts with expert parallelism.

Two code paths sharing the router math:

* ``moe_dense``   — every expert applied to every token, combined by gates.
  Exact, O(E/topk) too much compute; used for tiny smoke configs and as the
  test oracle for the sharded path.
* ``moe_sharded`` — the production path: shard_map over the mesh, experts
  sharded along the ``model`` axis. Per model-shard token slice ->
  sort-based pack into a fixed-capacity (E, C, D) buffer -> all_to_all
  (dispatch) -> local expert FFN -> all_to_all (return) -> unpack/combine ->
  all_gather tokens. This is the DeepSeek-style EP schedule expressed in
  jax.lax collectives; XLA overlaps the two all_to_alls with the shared
  expert running outside.

Capacity C = ceil(topk * tokens / E * capacity_factor) tokens are kept per
expert (sorted by arrival order); overflow tokens fall back to their gate
mass being dropped (standard token-dropping MoE).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..distributed import sharding as shlib
from .common import ParamBuilder, sub

Array = jax.Array


def init_moe(pb: ParamBuilder, tree, specs, cfg):
    e, dff = cfg.num_experts, cfg.moe_d_ff
    t, s = sub(tree, specs, "moe")
    pb.make(t, s, [], "router", (cfg.d_model, e), ("embed", None))
    pb.make(t, s, [], "w_gate", (e, cfg.d_model, dff),
            ("experts", "moe_mlp", None))
    pb.make(t, s, [], "w_up", (e, cfg.d_model, dff),
            ("experts", "moe_mlp", None))
    pb.make(t, s, [], "w_down", (e, dff, cfg.d_model),
            ("experts", None, "moe_mlp"))


def _route(cfg, router_w, x_flat):
    """x_flat (n, D) -> (gates (n,k), eids (n,k), aux losses)."""
    logits = (x_flat @ router_w.astype(x_flat.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eids = lax.top_k(probs, cfg.experts_per_token)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    # Shazeer load-balance aux: E * sum_e f_e * P_e
    e = cfg.num_experts
    f = jnp.mean(jax.nn.one_hot(eids, e, dtype=jnp.float32), axis=(0, 1))
    pmean = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(f * pmean)
    zloss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return gates.astype(x_flat.dtype), eids, {"load_balance": aux,
                                              "router_z": zloss}


def moe_dense(cfg, p, x: Array):
    """(B,T,D) exact all-experts path (smoke/test oracle)."""
    b, t, d = x.shape
    xf = x.reshape(b * t, d)
    gates, eids, aux = _route(cfg, p["router"], xf)
    h_gate = jnp.einsum("nd,edf->enf", xf, p["w_gate"].astype(x.dtype))
    h_up = jnp.einsum("nd,edf->enf", xf, p["w_up"].astype(x.dtype))
    y_e = jnp.einsum("enf,efd->end", jax.nn.silu(h_gate) * h_up,
                     p["w_down"].astype(x.dtype))
    comb = jnp.zeros((xf.shape[0], cfg.num_experts), x.dtype)
    comb = comb.at[jnp.arange(xf.shape[0])[:, None], eids].add(gates)
    y = jnp.einsum("ne,end->nd", comb, y_e)
    return y.reshape(b, t, d), aux


def _capacity(cfg, n_tokens: int) -> int:
    c = math.ceil(cfg.experts_per_token * n_tokens / cfg.num_experts
                  * cfg.capacity_factor)
    return max(4, -(-c // 4) * 4)


def _pack_local(cfg, xs, gates, eids, cap):
    """Sort-based pack: xs (n,D) -> buf (E*C, D); returns buf, scatter meta."""
    n, d = xs.shape
    k = cfg.experts_per_token
    flat_e = eids.reshape(n * k)
    flat_tok = jnp.repeat(jnp.arange(n), k)
    flat_gate = gates.reshape(n * k)
    order = jnp.argsort(flat_e)
    e_sorted = flat_e[order]
    counts = jnp.bincount(flat_e, length=cfg.num_experts)
    starts = jnp.cumsum(counts) - counts
    rank = jnp.arange(n * k) - starts[e_sorted]
    keep = rank < cap
    dest = jnp.where(keep, e_sorted * cap + rank, cfg.num_experts * cap)
    buf = jnp.zeros((cfg.num_experts * cap + 1, d), xs.dtype)
    buf = buf.at[dest].add(xs[flat_tok[order]])
    return buf[:-1], (order, flat_tok, flat_gate, dest, keep)


def _unpack_local(cfg, y_buf, meta, n, d):
    order, flat_tok, flat_gate, dest, keep = meta
    y_slot = jnp.concatenate([y_buf, jnp.zeros((1, d), y_buf.dtype)], 0)[dest]
    w = jnp.where(keep, flat_gate[order], 0.0)[:, None].astype(y_buf.dtype)
    y = jnp.zeros((n, d), y_buf.dtype)
    return y.at[flat_tok[order]].add(w * y_slot)


def _plain_a2a(v, split, concat):
    return lax.all_to_all(v, "model", split_axis=split, concat_axis=concat,
                          tiled=True)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _qa2a(v, split, concat):
    """int8-on-the-wire all_to_all (beyond-paper EP optimisation): values are
    quantised per (expert, slot) row before the collective, scales ride
    along; the BACKWARD all_to_all is quantised the same way (custom_vjp),
    so both directions move ~2x (vs bf16) / ~4x (vs f32) fewer bytes."""
    out, _ = _qa2a_fwd(v, split, concat)
    return out


def _quant_pair(v, split, concat):
    sc = jnp.maximum(jnp.max(jnp.abs(v), axis=-1, keepdims=True), 1e-12) / 127.0
    q = jnp.clip(jnp.round(v / sc), -127, 127).astype(jnp.int8)
    q_r = lax.all_to_all(q, "model", split_axis=split, concat_axis=concat,
                         tiled=True)
    sc_r = lax.all_to_all(sc, "model", split_axis=split, concat_axis=concat,
                          tiled=True)
    return (q_r.astype(jnp.float32) * sc_r).astype(v.dtype)


def _qa2a_fwd(v, split, concat):
    return _quant_pair(v, split, concat), None


def _qa2a_bwd(split, concat, _, g):
    # transpose of all_to_all swaps split/concat; quantise the cotangent too
    return (_quant_pair(g, concat, split),)


_qa2a.defvjp(_qa2a_fwd, _qa2a_bwd)


def _expert_ffn(p, xb, dtype):
    """xb (E_loc, C', D) with local expert weights."""
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xb, p["w_gate"].astype(dtype)))
    u = jnp.einsum("ecd,edf->ecf", xb, p["w_up"].astype(dtype))
    return jnp.einsum("ecf,efd->ecd", g * u, p["w_down"].astype(dtype))


def moe_sharded(cfg, p, x: Array):
    """Expert-parallel MoE via shard_map + all_to_all (see module doc)."""
    mesh = shlib._CTX["mesh"]
    if mesh is None or "model" not in mesh.shape:
        return moe_dense(cfg, p, x)
    em = mesh.shape["model"]
    if cfg.num_experts % em != 0:
        return moe_dense(cfg, p, x)
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)

    x_spec = P(batch_axes, None, None)
    w_specs = {"router": P(), "w_gate": P("model"), "w_up": P("model"),
               "w_down": P("model")}

    def block(xl, pl_):
        b_loc, t, d = xl.shape
        n = b_loc * t
        pad = (-n) % em
        xf = xl.reshape(n, d)
        if pad:
            xf = jnp.pad(xf, ((0, pad), (0, 0)))
        n_p = xf.shape[0]
        per = n_p // em
        i = lax.axis_index("model")
        xs = lax.dynamic_slice_in_dim(xf, i * per, per, axis=0)   # (per, D)

        gates, eids, aux = _route(cfg, pl_["router"], xs)
        if pad:  # zero the gates of padded tokens
            tok_id = i * per + jnp.arange(per)
            gates = jnp.where((tok_id < n)[:, None], gates, 0.0)
        cap = _capacity(cfg, per)
        buf, meta = _pack_local(cfg, xs, gates, eids, cap)        # (E*C, D)
        buf = buf.reshape(cfg.num_experts, cap, d)
        a2a = (_qa2a if cfg.moe_dispatch_dtype == "int8"
               else _plain_a2a)
        recv = a2a(buf, 0, 1)                                     # (E_loc, em*C, D)
        y_loc = _expert_ffn(pl_, recv, x.dtype)
        back = a2a(y_loc, 1, 0)                                   # (E, C, D)
        y_s = _unpack_local(cfg, back.reshape(cfg.num_experts * cap, d),
                            meta, per, d)                          # (per, D)
        y_full = lax.all_gather(y_s, "model", axis=0, tiled=True)  # (n_p, D)
        y = y_full[:n].reshape(b_loc, t, d)
        aux = {k: lax.pmean(v, "model") for k, v in aux.items()}
        return y, aux

    fn = jax.shard_map(block, mesh=mesh, in_specs=(x_spec, w_specs),
                       out_specs=(x_spec, P()), check_vma=False)
    return fn(x, {k: p[k] for k in w_specs})


def moe_forward(cfg, p, x: Array):
    if cfg.moe_impl == "dense":
        return moe_dense(cfg, p, x)
    return moe_sharded(cfg, p, x)
