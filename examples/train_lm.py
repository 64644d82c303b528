"""End-to-end driver example: train a ~100M-param llama-style model for a
few hundred steps with the production substrate (data pipeline, grad
accumulation, checkpointing, straggler monitor), selectable paper
optimizers included.

    PYTHONPATH=src python examples/train_lm.py --steps 300
(CPU: takes a while; use --d-model 256 --layers 4 for a fast demo)
"""
import argparse

import jax

from repro import configs
from repro.data import pipeline as dp
from repro.launch.mesh import make_host_mesh
from repro.models import build
from repro.models.sharding import use_mesh
from repro.train import checkpoint as ckpt
from repro.train import optimizer as opt_mod
from repro.train.straggler import StepMonitor
from repro.train.train_step import build_train_step
from repro import compile_cache

compile_cache.enable()

ap = argparse.ArgumentParser()
ap.add_argument("--steps", type=int, default=300)
ap.add_argument("--d-model", type=int, default=768)
ap.add_argument("--layers", type=int, default=12)
ap.add_argument("--optimizer", default="adamw")
ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
args = ap.parse_args()

# ~100M params: 12L × 768d, llama3-style
cfg = configs.get("llama3.2-3b").scaled(
    num_layers=args.layers, d_model=args.d_model, num_heads=12,
    num_kv_heads=4, head_dim=64, d_ff=2048, vocab_size=32000,
    dtype="float32", remat="none")
mesh = make_host_mesh()

with mesh, use_mesh(mesh):
    model = build(cfg)
    n_params = sum(x.size for x in jax.tree.leaves(
        model.specs()[0]))
    print(f"model: {n_params/1e6:.1f}M params")
    ocfg = opt_mod.OptimizerConfig(name=args.optimizer, lr=3e-4,
                                   warmup_steps=20, total_steps=args.steps)
    opt_init, opt_update = opt_mod.make_optimizer(ocfg)
    step = jax.jit(build_train_step(model, opt_update, microbatches=2),
                   donate_argnums=(0, 1))
    dc = dp.from_model(cfg, global_batch=8, seq_len=128)
    batch_fn = jax.jit(lambda s: dp.in_graph_batch(dc, s))

    params = model.init(jax.random.PRNGKey(0))
    opt_state = opt_init(params)
    saver = ckpt.AsyncCheckpointer(args.ckpt_dir)
    mon = StepMonitor()
    for s in range(args.steps):
        mon.start()
        params, opt_state, m = step(params, opt_state, batch_fn(s))
        v = mon.stop()
        if s % 20 == 0 or s == args.steps - 1:
            print(f"step {s:4d} loss={float(m['loss']):.4f} "
                  f"dt={v['dt']*1e3:.0f}ms")
        if (s + 1) % 100 == 0:
            saver.save_async(s + 1, (params, opt_state),
                             extra={"data_step": s + 1})
    saver.wait()
    print("done; checkpoints in", args.ckpt_dir)
