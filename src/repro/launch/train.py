"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

On this CPU container it trains *reduced* configs end-to-end (the full
configs are dry-run-only); on a real fleet the same entrypoint runs the full
mesh with the XLA latency-hiding-scheduler flags below.
"""
from __future__ import annotations

import os

# Compute/communication overlap: enable XLA's latency-hiding scheduler and
# async collectives when we are on a real accelerator fleet.
if os.environ.get("REPRO_REAL_FLEET"):
    os.environ.setdefault("LIBTPU_INIT_ARGS", " ".join([
        "--xla_enable_async_all_gather=true",
        "--xla_enable_async_collective_permute=true",
        "--xla_tpu_enable_async_collective_fusion=true",
        "--xla_latency_hiding_scheduler_rerun=2",
    ]))

import argparse
import contextlib
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.configs import base as cfgbase
from repro.data.synthetic import token_batches
from repro.launch import mesh as meshlib
from repro.launch.steps import batch_pspecs, make_train_step
from repro.models import api
from repro.models.lm_common import NO_SHARD
from repro.train import optimizer as opt
from repro.train.loop import LoopConfig, train_loop


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--reduced", action="store_true", default=True,
                    help="train the reduced config (CPU container default)")
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--mesh", default="none", choices=["none", "single", "multi"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--trace", default=None, metavar="OUT",
                    help="trace the training loop: Chrome trace-event JSON "
                         "(or .jsonl event log) with per-step/checkpoint "
                         "spans, plus an XLA profile in OUT.xprof/")
    args = ap.parse_args()

    cfg = cfgbase.get_arch(args.arch)
    if args.reduced:
        cfg = cfgbase.reduced(cfg)

    if args.mesh == "none":
        mesh, ctx = None, NO_SHARD
    else:
        mesh = meshlib.make_production_mesh(multi_pod=args.mesh == "multi")
        ctx = meshlib.make_ctx(mesh)

    params = api.init_params(cfg, jax.random.PRNGKey(0))
    ocfg = opt.AdamWConfig(lr=args.lr)
    state = opt.init_opt_state(params, ocfg)
    if mesh is not None:
        pspecs = api.param_pspecs(cfg, params, ctx)
        shd = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                           is_leaf=lambda x: isinstance(x, P))
        params = jax.device_put(params, shd)

    def raw_step(params, state, batch):
        loss, grads = jax.value_and_grad(partial(api.loss_fn, cfg, ctx=ctx))(params, batch)
        p2, s2, gnorm = opt.adamw_update(params, grads, state, ocfg)
        return p2, s2, {"loss": loss, "grad_norm": gnorm}

    step = jax.jit(raw_step, donate_argnums=(0, 1))
    data = token_batches(0, args.batch, args.seq, cfg.vocab)

    def wrap(it):
        for b in it:
            if not cfg.embed_input:
                emb = jax.nn.one_hot(b["tokens"] % cfg.d_model, cfg.d_model, dtype=cfg.jdtype)
                b = {"embeds": emb, "labels": b["labels"]}
            if cfg.cross_every:
                b["img_emb"] = jnp.zeros((args.batch, cfg.n_img_tokens, cfg.d_model), cfg.jdtype)
            yield b

    lcfg = LoopConfig(total_steps=args.steps, ckpt_every=args.ckpt_every,
                      ckpt_dir=args.ckpt_dir, log_every=10)
    if args.trace:
        obs.enable()
    profiler = (obs.jax_profile(args.trace + ".xprof")
                if args.trace else contextlib.nullcontext())
    with profiler:
        params, state, report = train_loop(step, params, state, wrap(data),
                                           lcfg)
    print(f"done: {report.steps_run} steps, final metrics {report.last_metrics}, "
          f"stragglers={report.straggler_steps}, "
          f"mean_step={sum(report.step_times) / max(len(report.step_times), 1):.3f}s")
    if args.trace:
        path = obs.export(obs.get_tracer(), args.trace)
        snap = obs.get_tracer().snapshot()
        print(f"trace: {snap['spans']} spans -> {path}"
              + f" (+ XLA profile in {args.trace}.xprof/)")


if __name__ == "__main__":
    main()
