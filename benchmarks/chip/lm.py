"""The training job of a decoder-only configuration: the program's train
step built from the configuration file, and the inputs and weights the
benchmark makes from the seed.

Weights and batches come from here, not from the program, so that the
plain reference (``reference/decoder_lm.py``) starts from the same numbers
without taking anything the program made.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int) -> jax.Array:
    """A PRNG key that depends on all 64 bits of ``seed``."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def param_shapes(cfg: dict) -> dict[str, tuple[tuple[int, ...], str]]:
    """Name -> (shape, init) of every parameter, layers stacked on dim 0."""
    D, L, F, V = (cfg["hidden_size"], cfg["num_hidden_layers"],
                  cfg["intermediate_size"], cfg["vocab_size"])
    hd = cfg["head_dim"]
    Hq, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {
        "embed": ((V, D), "normal"),
        "final_norm": ((D,), "zeros"),
        "ln1": ((L, D), "zeros"),
        "ln2": ((L, D), "zeros"),
        "wq": ((L, D, Hq * hd), "normal"),
        "wk": ((L, D, KV * hd), "normal"),
        "wv": ((L, D, KV * hd), "normal"),
        "wo": ((L, Hq * hd, D), "normal"),
        "w_gate": ((L, D, F), "normal"),
        "w_up": ((L, D, F), "normal"),
        "w_down": ((L, F, D), "normal"),
    }


def make_params(cfg: dict, key: jax.Array) -> dict[str, jax.Array]:
    """Parameters in the configuration's dtype: normal(0, initializer_range)
    matrices, zero norm offsets (the norms scale by ``1 + w``)."""
    dtype = jnp.dtype(cfg["dtype"])
    out = {}
    for i, (name, (shape, init)) in enumerate(sorted(param_shapes(cfg).items())):
        if init == "zeros":
            out[name] = jnp.zeros(shape, dtype)
        else:
            out[name] = (cfg["initializer_range"] * jax.random.normal(
                jax.random.fold_in(key, i), shape, jnp.float32)).astype(dtype)
    return out


def make_state(cfg: dict, key: jax.Array) -> dict[str, jax.Array]:
    """The flat train state ``{"params/..", "opt/m/..", "opt/v/..", "step"}``
    at step 0: the seed's weights and zero AdamW moments."""
    params = make_params(cfg, key)
    mdt = jnp.dtype(cfg["optimizer"]["state_dtype"])
    state = {f"params/{n}": v for n, v in params.items()}
    for slot in ("m", "v"):
        state.update({f"opt/{slot}/{n}": jnp.zeros(v.shape, mdt)
                      for n, v in params.items()})
    state["step"] = jnp.zeros((), jnp.int32)
    return state


class Feed:
    """Global batches of the job: batch ``i`` is a pure function of
    ``(seed, i)``, uniform token ids, every row different.  The Trainer's
    data interface (``batch``, ``state``)."""

    def __init__(self, seed: int, vocab: int, seq_len: int, batch: int):
        self.seed, self.vocab = seed, vocab
        self.seq_len, self.global_batch = seq_len, batch

    def batch(self, step: int) -> dict[str, np.ndarray]:
        rng = np.random.Generator(np.random.Philox(key=[self.seed, step]))
        tokens = rng.integers(0, self.vocab, (self.global_batch,
                                              self.seq_len + 1),
                              dtype=np.int32)
        mask = np.ones((self.global_batch, self.seq_len), np.float32)
        mask[:, -1] = 0.0
        return {"tokens": tokens[:, :-1], "targets": tokens[:, 1:].copy(),
                "mask": mask}

    def state(self, next_step: int) -> dict:
        return {"pipeline_seed": self.seed, "next_step": int(next_step)}


def model_config(cfg: dict):
    """The program's ``ModelConfig`` for this configuration file."""
    from repro.configs.base import ModelConfig

    return ModelConfig(
        arch=cfg["name"], family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        head_dim=cfg["head_dim"], rope_theta=cfg["rope_theta"],
        tie_embeddings=cfg["tie_word_embeddings"], dtype=cfg["dtype"],
        source=cfg["source"])


def schedule_fn(job: dict):
    from repro.train.schedule import warmup_cosine

    s = job["schedule"]
    return functools.partial(warmup_cosine, base_lr=s["base_lr"],
                             warmup=s["warmup"], total=s["total"])


def train_step(cfg: dict, job: dict, mesh):
    """The program's jitted train step for this configuration and job."""
    from repro.configs.base import ShapeConfig
    from repro.distrib.rules import rules_for
    from repro.models.api import build_model
    from repro.train.optim import AdamW
    from repro.train.step import make_train_step

    mc = model_config(cfg)
    o = cfg["optimizer"]
    opt = AdamW(b1=o["b1"], b2=o["b2"], eps=o["eps"],
                weight_decay=o["weight_decay"])
    return make_train_step(
        build_model(mc), opt, schedule_fn(job), mesh, rules_for(mc.arch),
        ShapeConfig("bench", job["seq_len"], job["global_batch"], "train"))


def model_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward FLOPs per token of a decoder-only transformer,
    recomputation excluded: 6 per parameter used in a matrix product
    (embedding gather excluded, tied output head included) plus causal
    attention's two S x S products, 6 x 2 x S/2 x heads x head_dim per
    layer (half the S x S matrix under the causal mask)."""
    D, L, F, V = (cfg["hidden_size"], cfg["num_hidden_layers"],
                  cfg["intermediate_size"], cfg["vocab_size"])
    hd = cfg["head_dim"]
    Hq, KV = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    per_layer = D * Hq * hd + 2 * D * KV * hd + Hq * hd * D + 3 * D * F
    matmul_params = L * per_layer + V * D
    attention = L * 2 * (seq_len / 2) * Hq * hd
    return 6.0 * (matmul_params + attention)


def tree_nbytes(tree: dict) -> int:
    return int(sum(math.prod(v.shape) * jnp.dtype(v.dtype).itemsize
                   for v in tree.values()))
