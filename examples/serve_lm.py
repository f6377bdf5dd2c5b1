"""Serve a small LM with batched requests: prefill + greedy decode with a
KV cache, reporting tokens/s.

    PYTHONPATH=src python examples/serve_lm.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, RunConfig
from repro.models.model import build_model
from repro.models.module import init_params, param_count
from repro.runtime.steps import make_serve_step
from repro.compile_cache import enable_compile_cache

CFG = ModelConfig(name="demo-serve-25m", family="dense", n_layers=6,
                  d_model=512, n_heads=8, n_kv_heads=4, d_ff=1408,
                  vocab=32000, act="swiglu")


def main():
    enable_compile_cache()
    run = RunConfig(remat="none", attn_chunk_q=64, attn_chunk_kv=64)
    model = build_model(CFG)
    params = init_params(model.specs, jax.random.key(0))
    print(f"[serve_lm] {param_count(model.specs)/1e6:.1f}M params")

    batch, prompt_len, gen = 8, 64, 32
    max_len = prompt_len + gen
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(rng.integers(0, CFG.vocab, (batch, prompt_len)),
                          jnp.int32)

    prefill = jax.jit(lambda p, t: model.prefill(p, run, t, max_len))
    serve_step = jax.jit(make_serve_step(model, run))

    # Warm-up compiles.
    logits, cache = prefill(params, prompts)
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
    tok, cache = serve_step(params, tok, cache)

    t0 = time.perf_counter()
    logits, cache = prefill(params, prompts)
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
    jax.block_until_ready(tok)
    t_prefill = time.perf_counter() - t0

    toks = [tok]
    t0 = time.perf_counter()
    for _ in range(gen - 1):
        tok, cache = serve_step(params, tok, cache)
        toks.append(tok)
    jax.block_until_ready(tok)
    t_dec = time.perf_counter() - t0

    out = np.asarray(jnp.concatenate(toks, 1))
    print(f"[serve_lm] prefill {batch}x{prompt_len}: "
          f"{batch*prompt_len/t_prefill:.0f} tok/s; decode: "
          f"{batch*(gen-1)/t_dec:.0f} tok/s")
    print("[serve_lm] first sequence:", out[0][:16])
    assert out.shape == (batch, gen)


if __name__ == "__main__":
    main()
