"""LLM serving demo: batched prefill + token-by-token decode.

    PYTHONPATH=src python examples/serve_llm.py --arch qwen3-4b --smoke \
        --batch 4 --prompt-len 32 --gen 16

Demonstrates the full generation path (prefill → KV/SSM cache → decode loop
→ greedy sampling) on real devices; the same prefill/decode functions are
what the dry-run lowers at production shapes.  (This used to live at
repro/launch/serve.py; that module is now the *solver* serving frontend —
the request-batched linear-algebra server.)
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.launch.mesh import make_host_mesh
from repro.models import build, smoke_config
from repro.models.sharding import use_mesh
from repro import compile_cache

compile_cache.enable()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    args = ap.parse_args()

    cfg = configs.get(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    mesh = make_host_mesh(args.data, args.model)
    rng = np.random.default_rng(0)

    with mesh, use_mesh(mesh):
        model = build(cfg)
        params = model.init(jax.random.PRNGKey(0))
        B, S = args.batch, args.prompt_len
        total = S + args.gen
        batch = {"tokens": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (B, S)), jnp.int32)}
        if cfg.frontend:
            flen = S if cfg.family == "encdec" else cfg.frontend_len
            batch["frontend_embeds"] = jnp.asarray(
                rng.normal(size=(B, flen, cfg.d_model)) * 0.02, jnp.float32)
        if cfg.family == "encdec":
            caches, _ = model.init_caches(B, total, S)
        else:
            caches, _ = model.init_caches(B, total)

        prefill = jax.jit(model.prefill)
        decode = jax.jit(model.decode_step, donate_argnums=(2,))

        t0 = time.time()
        logits, caches = prefill(params, batch, caches)
        logits.block_until_ready()
        t_prefill = time.time() - t0

        out_tokens = [jnp.argmax(logits[:, -1], -1)[:, None]]
        pos = jnp.int32(S)
        t0 = time.time()
        for _ in range(args.gen - 1):
            logits, caches = decode(params, out_tokens[-1], caches, pos)
            out_tokens.append(jnp.argmax(logits[:, -1], -1)[:, None])
            pos = pos + 1
        jax.block_until_ready(out_tokens[-1])
        t_decode = time.time() - t0

        gen = np.asarray(jnp.concatenate(out_tokens, 1))
        print(f"prefill: {t_prefill*1e3:.1f}ms for {B}x{S} tokens")
        print(f"decode : {t_decode/max(args.gen-1,1)*1e3:.1f}ms/token "
              f"(batch {B})")
        print("generated token ids (first row):", gen[0][:16])


if __name__ == "__main__":
    main()
