"""Mixture-of-Experts FFN — two implementations:

``moe_ffn`` (dense dispatch): GShard-style one-hot dispatch/combine
einsums.  O(B*S*E*C) memory — only feasible for small configs; it is the
*oracle* the EP path is validated against (tests/test_moe_ep.py).

``moe_ffn_ep`` (expert-parallel, shard_map): the production path.
Exploits the tensor-parallel invariant that activations are replicated
across the "model" axis: every model shard routes the *same* tokens,
keeps only the choices that hit its local experts, scatters them into a
capacity buffer by sorted position-in-expert, runs its experts, scatters
back, and a single psum over the model axis combines — the only
cross-shard communication on the dispatch path is the combine psum (plus
the ZeRO-3 all-gather of the expert weights over the fsdp axis).  Memory
per device is O(T_local * top_k / E * cf * D) for the capacity buffers:
feasible at kimi-k2 scale where the one-hot dispatch tensor would be
~10^13 elements.

Experts that do not divide the model-axis size are padded (zero weights)
and router-masked upstream; the EP path only sees the padded count.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

F32 = jnp.float32


def moe_ffn(x, router_w, w_gate, w_up, w_down, *, top_k: int,
            capacity_factor: float = 1.25, num_real: int | None = None):
    """x [B, S, D]; router_w [D, E]; experts w_gate/w_up [E, D, F],
    w_down [E, F, D].  Returns (y [B, S, D], aux_loss scalar).
    ``num_real`` masks router-padded phantom experts (< E)."""
    B, S, D = x.shape
    E = router_w.shape[-1]
    C = max(1, int(S * top_k / E * capacity_factor))

    logits = (x.astype(F32) @ router_w.astype(F32))          # [B,S,E]
    if num_real is not None and num_real < E:
        logits = jnp.where(jnp.arange(E) >= num_real, -1e30, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, top_k)                 # [B,S,k]
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # GShard position-in-expert via k cumsum passes over the sequence
    dispatch = jnp.zeros((B, S, E, C), dtype=x.dtype)
    combine = jnp.zeros((B, S, E, C), dtype=F32)
    fill = jnp.zeros((B, E), dtype=jnp.int32)                # expert fill count
    for j in range(top_k):
        onehot_e = jax.nn.one_hot(ids[..., j], E, dtype=jnp.int32)   # [B,S,E]
        pos = fill[:, None, :] + jnp.cumsum(onehot_e, axis=1) - onehot_e
        pos = pos * onehot_e                                  # position where routed
        keep = (onehot_e > 0) & (pos < C)
        pos_oh = jax.nn.one_hot(pos, C, dtype=x.dtype) * keep[..., None]
        dispatch = dispatch + pos_oh * onehot_e[..., None].astype(x.dtype)
        combine = combine + (pos_oh.astype(F32)
                             * onehot_e[..., None].astype(F32)
                             * gates[..., j][..., None, None])
        fill = fill + jnp.sum(onehot_e, axis=1)

    # dispatch tokens -> expert buffers [E, B, C, D]
    xe = jnp.einsum("bsec,bsd->ebcd", dispatch, x)
    h = jax.nn.silu(jnp.einsum("ebcd,edf->ebcf", xe, w_gate)) \
        * jnp.einsum("ebcd,edf->ebcf", xe, w_up)
    ye = jnp.einsum("ebcf,efd->ebcd", h, w_down)
    y = jnp.einsum("bsec,ebcd->bsd", combine.astype(x.dtype), ye)

    # Switch-style load-balance aux loss
    frac_tokens = jnp.mean(
        jax.nn.one_hot(ids, E, dtype=F32).sum(2), axis=(0, 1)) / top_k
    frac_probs = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(frac_tokens * frac_probs)
    return y, aux


# ===================================================================== EP path
def _route(x_flat, router_w, *, top_k: int, num_real: int):
    """Shared routing: returns (gates [T,k] f32, ids [T,k] i32, probs [T,E])."""
    E = router_w.shape[-1]
    logits = x_flat.astype(F32) @ router_w.astype(F32)            # [T, E]
    if num_real < E:                                              # mask pads
        pad_mask = jnp.arange(E) >= num_real
        logits = jnp.where(pad_mask[None, :], -1e30, logits)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, ids = jax.lax.top_k(probs, top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, ids.astype(jnp.int32), probs


def _ep_body(x, router_w, w_gate, w_up, w_down, *, top_k: int,
             capacity: int, num_real: int, num_experts: int,
             ep_axis: str, fsdp_axis: str | None, dp_axes: tuple[str, ...]):
    """Per-device body under shard_map.

    x [B_loc, S, D] — the local batch shard, REPLICATED across ep_axis.
    w_* [E_loc, D_loc, F] / [E_loc, F, D_loc] — local experts, optionally
    ZeRO-3-sharded over fsdp_axis on the D dim.
    """
    B, S, D_in = x.shape
    # ZeRO-3: gather the expert weights' embed dim (backward: reduce-scatter)
    if fsdp_axis:
        w_gate = jax.lax.all_gather(w_gate, fsdp_axis, axis=1, tiled=True)
        w_up = jax.lax.all_gather(w_up, fsdp_axis, axis=1, tiled=True)
        w_down = jax.lax.all_gather(w_down, fsdp_axis, axis=2, tiled=True)
    E_loc = w_gate.shape[0]
    D = w_gate.shape[1]
    x_flat = x.reshape(B * S, D)
    T = B * S

    gates, ids, probs = _route(x_flat, router_w, top_k=top_k,
                               num_real=num_real)

    # ---- keep only choices routed to my experts -------------------------
    my_lo = jax.lax.axis_index(ep_axis).astype(jnp.int32) * E_loc
    eid = ids.reshape(T * top_k)
    gate = gates.reshape(T * top_k)
    tok = jnp.repeat(jnp.arange(T, dtype=jnp.int32), top_k)
    local_e = eid - my_lo
    mine = (local_e >= 0) & (local_e < E_loc)
    key = jnp.where(mine, local_e, E_loc).astype(jnp.int32)       # E_loc = trash

    # ---- position-in-expert via sort (deterministic, cone-stable order) -
    # NB: the val operand must be explicitly pcast to varying over ep_axis.
    # With an invariant val, JAX's VMA typing marks the returned permutation
    # invariant even though the (varying) key makes it shard-dependent, and
    # the shard_map transpose then miscomputes gradients (validated by
    # tests/helpers/moe_ep_check.py; forward is unaffected).
    arange_v = jax.lax.pcast(jnp.arange(T * top_k, dtype=jnp.int32),
                             (ep_axis,), to="varying")
    key_s, perm = jax.lax.sort_key_val(key, arange_v)
    counts = jnp.bincount(key_s, length=E_loc + 1)
    starts = jnp.concatenate([jnp.zeros(1, counts.dtype),
                              jnp.cumsum(counts)])[:-1]
    pos = jnp.arange(T * top_k, dtype=jnp.int32) - starts[key_s]
    keep = (key_s < E_loc) & (pos < capacity)

    dest = jnp.where(keep, key_s * capacity + pos, E_loc * capacity)
    tok_s = tok[perm]
    gate_s = gate[perm]

    # ---- dispatch: scatter tokens into capacity buffers ------------------
    xe = jnp.zeros((E_loc * capacity, D), x.dtype)
    xe = xe.at[dest].add(x_flat[tok_s] * keep[:, None].astype(x.dtype),
                         mode="drop")
    xe = xe.reshape(E_loc, capacity, D)

    # ---- expert FFN -------------------------------------------------------
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, w_gate)) \
        * jnp.einsum("ecd,edf->ecf", xe, w_up)
    ye = jnp.einsum("ecf,efd->ecd", h, w_down).reshape(E_loc * capacity, D)

    # ---- combine: gather back, weight by gates, psum over experts --------
    vals = ye.at[dest].get(mode="fill", fill_value=0.0) \
        * (gate_s * keep.astype(F32)).astype(ye.dtype)[:, None]
    y_flat = jnp.zeros((T, D), ye.dtype).at[tok_s].add(vals)
    y = jax.lax.psum(y_flat.reshape(B, S, D), ep_axis)

    # ---- aux loss (identical across ep_axis; average over batch axes) ----
    frac_tokens = jnp.mean(
        (ids[..., None] == jnp.arange(num_real)[None, None]).astype(F32)
        .sum(1), axis=0)
    frac_probs = jnp.mean(probs[:, :num_real], axis=0)
    # global means BEFORE the product (E[X]E[Y], matching the oracle's
    # global-batch statistics), not a mean of per-shard products
    frac_tokens = jax.lax.pmean(frac_tokens, dp_axes)
    frac_probs = jax.lax.pmean(frac_probs, dp_axes)
    aux = num_real * jnp.sum(frac_tokens / top_k * frac_probs)
    return y, aux


def moe_ffn_ep(x, router_w, w_gate, w_up, w_down, *, top_k: int,
               capacity_factor: float, num_real: int, mesh,
               dp_axes: tuple[str, ...] = ("data",),
               ep_axis: str = "model", fsdp_axis: str | None = "data"):
    """Expert-parallel MoE FFN (production path).

    x [B, S, D] sharded over ``dp_axes`` on B; router_w [D, E] replicated;
    w_* [E, D, F]/[E, F, D] with E sharded over ``ep_axis`` and D over
    ``fsdp_axis``.  Returns (y [B, S, D] like x, aux scalar replicated).
    """
    B, S, D = x.shape
    E = w_gate.shape[0]
    ep = mesh.shape[ep_axis]
    assert E % ep == 0, f"{E} experts not divisible by {ep_axis}={ep}"
    dp = math.prod(mesh.shape[a] for a in dp_axes)
    t_loc = max(1, (B // max(dp, 1)) * S)
    capacity = max(1, int(math.ceil(t_loc * top_k / E * capacity_factor)))

    fsdp = fsdp_axis
    if isinstance(fsdp, str):
        fsdp = (fsdp,)
    if fsdp:
        k = math.prod(mesh.shape[a] for a in fsdp)
        if D % k != 0:
            fsdp = None                  # embed dim not divisible: no ZeRO-3
    fsdp = tuple(fsdp) if fsdp else None
    w_spec_gu = P(ep_axis, fsdp, None) if fsdp else P(ep_axis, None, None)
    w_spec_d = P(ep_axis, None, fsdp) if fsdp else P(ep_axis, None, None)
    body = functools.partial(
        _ep_body, top_k=top_k, capacity=capacity, num_real=num_real,
        num_experts=E, ep_axis=ep_axis, fsdp_axis=fsdp, dp_axes=dp_axes)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(dp_axes, None, None), P(None, None),
                  w_spec_gu, w_spec_gu, w_spec_d),
        out_specs=(P(dp_axes, None, None), P()),
    )
    return fn(x, router_w, w_gate, w_up, w_down)
