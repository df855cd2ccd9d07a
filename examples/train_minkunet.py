"""End-to-end driver: train a MinkUNet segmentation model on synthetic
LiDAR scenes for a few hundred steps, with the full production substrate —
AdamW, grad clipping, async checkpointing, resume, straggler watchdog —
executing through a compiled ``core.plan.NetworkPlan``.

    PYTHONPATH=src python examples/train_minkunet.py --steps 300 --width 1.0
    PYTHONPATH=src python examples/train_minkunet.py --precision bf16

(~100M-param model at --width 2.6; the default keeps CPU runtime sane.
``--precision bf16`` runs the paper's mixed-precision recipe: bf16 conv
params/activations, fp32 accumulation, fp32 master weights in AdamW.)
"""
import argparse
import os
import tempfile

import jax
import jax.numpy as jnp

from repro.core import dataflows as df
from repro.core import precision as prec
from repro.core.sparse_conv import TrainDataflowConfig
from repro.data.synthetic import lidar_scene
from repro.launch.compile_cache import enable_compile_cache
from repro.models import minkunet
from repro.train import optimizer as opt
from repro.train.loop import LoopConfig, train_loop


def build_trainer(cfg: minkunet.MinkUNetConfig, policy, cfg3: TrainDataflowConfig,
                  points: int, capacity: int, seed: int = 0):
    """The jitted AdamW train step of ``cfg`` with every layer group bound
    to ``cfg3`` under ``policy``.  Returns ``(params, opt_state, step,
    data)``: ``step(params, state, batch) → (params, state, metrics)`` and
    ``data()`` an endless iterator of ``{"scene", "labels"}`` batches."""
    nplan = minkunet.network_plan(cfg, precision=policy)
    nplan = nplan.with_assignment({lp.sig: cfg3 for lp in nplan.layers})
    params = nplan.cast_params(minkunet.init_params(cfg, jax.random.PRNGKey(seed)))
    ocfg = opt.AdamWConfig(lr=2e-3, weight_decay=0.01,
                           master_weights=policy.master_weights)
    state = opt.init_opt_state(params, ocfg)

    def data():
        i = 0
        while True:
            st = lidar_scene(jax.random.PRNGKey(seed + i), points, capacity,
                             cfg.in_channels, extent=40.0, voxel=0.5)
            # synthetic labels: height-band segmentation (learnable signal)
            z = st.coords[:, 3]
            labels = jnp.clip(z // 2, 0, cfg.num_classes - 1).astype(jnp.int32)
            yield {"scene": st, "labels": labels}
            i += 1

    @jax.jit
    def step(params, state, batch):
        st, labels = batch["scene"], batch["labels"]

        def loss_fn(p):
            lg = nplan.apply(p, st).astype(jnp.float32)
            ls = jax.nn.log_softmax(lg)[jnp.arange(st.capacity), labels]
            return -jnp.sum(jnp.where(st.valid_mask, ls, 0)) / jnp.maximum(st.num_valid, 1)

        l, g = jax.value_and_grad(loss_fn)(params)
        p2, s2, gn = opt.adamw_update(params, g, state, ocfg)
        return p2, s2, {"loss": l, "grad_norm": gn}

    return params, state, step, data


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--width", type=float, default=0.25)
    ap.add_argument("--points", type=int, default=1500)
    ap.add_argument("--capacity", type=int, default=2048)
    ap.add_argument("--classes", type=int, default=19)
    ap.add_argument("--ckpt-dir",
                    default=os.path.join(tempfile.gettempdir(), "minkunet_ckpt"))
    ap.add_argument("--dataflow", default="implicit_gemm", choices=df.DATAFLOWS)
    ap.add_argument("--precision", default="fp32", choices=sorted(prec.POLICIES),
                    help="numeric policy: fp32, or bf16 (bf16 compute / fp32 "
                         "accumulate / fp32 master weights)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = minkunet.MinkUNetConfig(in_channels=4, num_classes=args.classes,
                                  width=args.width, blocks_per_stage=1)
    policy = prec.POLICIES[args.precision]
    params, state, step, data = build_trainer(
        cfg, policy, TrainDataflowConfig.bind_all(df.DataflowConfig(args.dataflow)),
        args.points, args.capacity)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"MinkUNet width={args.width}: {n_params / 1e6:.1f}M params "
          f"({args.precision}, master_weights={policy.master_weights})")

    lcfg = LoopConfig(total_steps=args.steps, ckpt_every=50,
                      ckpt_dir=args.ckpt_dir, log_every=10)
    params, state, report = train_loop(step, params, state, data(), lcfg)
    print(f"finished {report.steps_run} steps "
          f"(resumed_from={report.resumed_from}); final {report.last_metrics}")


if __name__ == "__main__":
    main()
