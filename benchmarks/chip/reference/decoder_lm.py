"""Plain reference of the decoder-only training step: forward pass, loss,
gradients and AdamW in straightforward ``jax.numpy`` and float32 at
``highest`` matmul precision, with no kernels, no blocked attention and no
sharding.  It imports nothing of the program.

It follows the configuration file as the program runs it (see the file's
``departures``): token embeddings scaled by sqrt(hidden_size), RMS norms
that scale by ``1 + w`` with ``rms_norm_eps``, rotary embeddings on the two
halves of each head, grouped-query attention, SwiGLU, output head tied to
the embedding, mean token cross-entropy over the mask.  Parameters are kept
in the configuration's dtype between steps, as the program keeps them.

``quant`` rounds both operands of every matrix product to a lower
precision first: with ``float8_e4m3fn`` it is the lower-precision control
that the comparison must fail.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
LAYER_KEYS = ("ln1", "ln2", "wq", "wk", "wv", "wo", "w_gate", "w_up",
              "w_down")


def _rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos[:, None] * freq                                  # [S, half]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def row_loss(params, tokens, targets, mask, cfg, quant=None):
    """Summed cross-entropy of one sequence (``[S]`` ids) in float32."""
    q8 = (lambda a: a.astype(quant).astype(F32)) if quant else (lambda a: a)
    mm = lambda a, b: q8(a) @ q8(b)                            # noqa: E731
    D, hd = cfg["hidden_size"], cfg["head_dim"]
    Hq, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    S = tokens.shape[0]
    pos = jnp.arange(S, dtype=F32)
    causal = jnp.tril(jnp.ones((S, S), bool))

    def layer(x, lp):
        h = _rms_norm(x, lp["ln1"], eps)
        q = _rope(mm(h, lp["wq"]).reshape(S, Hq, hd), pos, theta)
        k = _rope(mm(h, lp["wk"]).reshape(S, KV, hd), pos, theta)
        v = mm(h, lp["wv"]).reshape(S, KV, hd)
        k = jnp.repeat(k, Hq // KV, axis=1)           # head j reads kv j // G
        v = jnp.repeat(v, Hq // KV, axis=1)
        s = jnp.einsum("qhd,khd->hqk", q8(q), q8(k)) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("hqk,khd->qhd", q8(p), q8(v)).reshape(S, Hq * hd)
        x = x + mm(o, lp["wo"])
        h = _rms_norm(x, lp["ln2"], eps)
        x = x + mm(jax.nn.silu(mm(h, lp["w_gate"])) * mm(h, lp["w_up"]),
                   lp["w_down"])
        return x, None

    x = params["embed"][tokens] * math.sqrt(D)
    x, _ = lax.scan(jax.checkpoint(layer), x,
                    {k: params[k] for k in LAYER_KEYS})
    h = _rms_norm(x, params["final_norm"], eps)
    logits = mm(h, params["embed"].T)
    picked = jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
    return jnp.sum((jax.nn.logsumexp(logits, -1) - picked) * mask)


def loss_and_grads(params, batch, cfg, quant=None):
    """Mean loss over the batch's mask and its gradient, one row at a time
    (the whole batch's attention matrices would not fit)."""
    grad_row = jax.value_and_grad(row_loss)

    def body(carry, row):
        total, grads = carry
        l, g = grad_row(params, row["tokens"], row["targets"], row["mask"],
                        cfg, quant)
        return (total + l, jax.tree.map(jnp.add, grads, g)), None

    zeros = jax.tree.map(jnp.zeros_like, params)
    (total, grads), _ = lax.scan(body, (F32(0.0), zeros), batch)
    count = jnp.sum(batch["mask"])
    return total / count, jax.tree.map(lambda g: g / count, grads)


def lr_at(step, sched):
    """``warmup_cosine`` written out: linear warm-up, then cosine decay to
    ``min_frac`` of the base rate."""
    step = jnp.asarray(step, F32)
    base, warm, total = sched["base_lr"], sched["warmup"], sched["total"]
    min_frac = sched.get("min_frac", 0.1)
    prog = jnp.clip((step - warm) / max(total - warm, 1), 0.0, 1.0)
    cos = min_frac + (1 - min_frac) * 0.5 * (1 + jnp.cos(jnp.pi * prog))
    return jnp.where(step < warm, base * jnp.minimum(step / max(warm, 1), 1.0),
                     base * cos)


def adamw(params, grads, m, v, step, lr, opt, dtype):
    """One AdamW update; new parameters rounded to the stored ``dtype``."""
    t = step + 1.0
    bc1, bc2 = 1.0 - opt["b1"] ** t, 1.0 - opt["b2"] ** t
    new_p, new_m, new_v = {}, {}, {}
    for n in params:
        g = grads[n]
        new_m[n] = opt["b1"] * m[n] + (1 - opt["b1"]) * g
        new_v[n] = opt["b2"] * v[n] + (1 - opt["b2"]) * g * g
        upd = (new_m[n] / bc1) / (jnp.sqrt(new_v[n] / bc2) + opt["eps"])
        upd = upd + opt["weight_decay"] * params[n]
        new_p[n] = (params[n] - lr * upd).astype(dtype).astype(F32)
    return new_p, new_m, new_v


def norms(tree) -> dict:
    return {n: jnp.sqrt(jnp.sum(jnp.square(a.astype(F32))))
            for n, a in tree.items()}


def follow(params, batches, cfg, sched, quant=None) -> dict:
    """Run ``len(batches)`` training steps from ``params`` (any float
    dtype).  Returns each step's loss, the per-leaf norms of the first
    step's gradient, and the per-leaf norms of the parameters' change
    after the last step, all as host numbers."""
    opt, dtype = cfg["optimizer"], jnp.dtype(cfg["dtype"])

    @jax.jit
    def step_fn(p, m, v, batch, step):
        with jax.default_matmul_precision("highest"):
            loss, g = loss_and_grads(p, batch, cfg, quant)
        lr = lr_at(step, sched)
        p, m, v = adamw(p, g, m, v, step, lr, opt, dtype)
        return p, m, v, loss, norms(g)

    p0 = {n: jnp.asarray(a, F32) for n, a in params.items()}
    p, m = dict(p0), jax.tree.map(jnp.zeros_like, p0)
    v = jax.tree.map(jnp.zeros_like, p0)
    losses, first = [], None
    for i, batch in enumerate(batches):
        p, m, v, loss, gn = step_fn(p, m, v, batch, jnp.float32(i))
        losses.append(float(loss))
        if first is None:
            first = {n: float(x) for n, x in gn.items()}
    change = jax.jit(lambda a, b: norms({n: a[n] - b[n] for n in a}))(p, p0)
    return {"losses": losses, "grad_norms": first,
            "change_norms": {n: float(x) for n, x in change.items()}}
