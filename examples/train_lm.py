"""End-to-end driver: train a ~100M-param dense LM for a few hundred steps
with geo-enriched synthetic data, checkpoints and an injected failure.

    PYTHONPATH=src python examples/train_lm.py [--steps 300]
"""
import argparse
import shutil

import jax

from repro.configs.base import ModelConfig, RunConfig
from repro.core.cells import build_cell_covering
from repro.core.fast import FastConfig, FastIndex
from repro.core.synth import build_synth_census
from repro.data.pipeline import make_source
from repro.models.model import build_model
from repro.models.module import init_params, param_count
from repro.optim import adamw
from repro.runtime.driver import DriverConfig, train_loop
from repro.runtime.steps import make_train_step
from repro.compile_cache import enable_compile_cache

# ~103M params: 12L x 768d, llama-style.
CFG = ModelConfig(name="demo-100m", family="dense", n_layers=12,
                  d_model=768, n_heads=12, n_kv_heads=4, d_ff=2048,
                  vocab=32000, act="swiglu")


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()
    shutil.rmtree(args.ckpt_dir, ignore_errors=True)

    run = RunConfig(remat="none", learning_rate=3e-4, schedule="cosine",
                    total_steps=args.steps, warmup_steps=20,
                    attn_chunk_q=128, attn_chunk_kv=128)
    model = build_model(CFG)
    params = init_params(model.specs, jax.random.key(0))
    opt = adamw.init(params)
    print(f"[example] {CFG.name}: {param_count(model.specs)/1e6:.1f}M params")

    # Geo-enriched pipeline: each sequence carries a location joined onto
    # the synthetic census via the paper's fast index.
    sc = build_synth_census(seed=1)
    cov = build_cell_covering(sc.census, max_level=8)
    geo = (FastIndex.from_covering(cov, sc.census, gbits=4),
           FastConfig(mode="approx"))

    class Shape:
        global_batch = args.batch
        seq_len = args.seq
    src = make_source(CFG, Shape, seed=0, geo=geo)

    step_fn = jax.jit(make_train_step(model, run))
    dcfg = DriverConfig(total_steps=args.steps, ckpt_every=100,
                        ckpt_dir=args.ckpt_dir, log_every=20)
    # Inject one failure mid-run to demonstrate checkpoint/restart.
    params, opt, hist = train_loop(step_fn, params, opt, src, dcfg,
                                   fail_at={args.steps // 2})
    print(f"[example] loss {hist['loss'][0]:.3f} -> {hist['loss'][-1]:.3f} "
          f"({hist['steps_run']} steps, {hist['restarts']} restart)")
    assert hist["loss"][-1] < hist["loss"][0]


if __name__ == "__main__":
    main()
