"""Serving launcher: batched prefill + decode driver.

Greedy-decodes a batch of synthetic prompts with the sharded KV cache,
reporting per-phase timings.  CPU-runnable with --smoke.
"""

from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.configs.base import ShapeConfig
from repro.distrib.rules import rules_for
from repro.launch.compile_cache import init_compile_cache
from repro.launch.mesh import make_debug_mesh, make_production_mesh
from repro.models.api import build_model, make_token_batch
from repro.train.step import make_decode_step, make_prefill_step


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--data-mesh", type=int, default=1)
    ap.add_argument("--model-mesh", type=int, default=1)
    ap.add_argument("--production-mesh", action="store_true")
    args = ap.parse_args(argv)
    init_compile_cache()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    api = build_model(cfg)
    mesh = (make_production_mesh() if args.production_mesh
            else make_debug_mesh(args.data_mesh, args.model_mesh))
    rules = rules_for(cfg.arch)
    B, P, G = args.batch, args.prompt_len, args.gen_len
    shape = ShapeConfig("serve", P, B, "prefill")
    cache_len = P + G

    prefill = make_prefill_step(api, mesh, rules, shape, cache_len=cache_len)
    decode = make_decode_step(
        api, mesh, rules, ShapeConfig("serve_dec", cache_len, B, "decode"))

    batch = make_token_batch(cfg, shape, seed=0)
    params = api.init(jax.random.key(0))
    t0 = time.time()
    logits, cache = prefill(params, batch)
    logits.block_until_ready()
    t_prefill = time.time() - t0

    toks = [jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None]]
    t1 = time.time()
    for i in range(G):
        step_batch = {"token": toks[-1],
                      "pos": jnp.full((B,), P + i, jnp.int32)}
        logits, cache = decode(params, cache, step_batch)
        toks.append(jnp.argmax(logits, axis=-1).astype(jnp.int32)[:, None])
    jax.block_until_ready(logits)
    t_decode = time.time() - t1

    out = np.concatenate([np.asarray(t) for t in toks], axis=1)
    print(json.dumps({
        "arch": cfg.arch,
        "batch": B, "prompt_len": P, "gen_len": G,
        "prefill_seconds": round(t_prefill, 3),
        "decode_seconds": round(t_decode, 3),
        "decode_tokens_per_s": round(B * G / max(t_decode, 1e-9), 1),
        "sample_tokens": out[0, :8].tolist(),
    }))


if __name__ == "__main__":
    main()
