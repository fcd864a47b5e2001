"""End-to-end driver: LoRA + WTA-CRS fine-tuning with the dataset-level
gradient-norm cache (Algorithm 1), fault-tolerant checkpointing, and
automatic bit-faithful resume — all through one RunSpec.

    PYTHONPATH=src python examples/finetune_lora_wtacrs.py \
        --arch xlstm-125m --steps 200 --ckpt-dir /tmp/wtacrs_ckpt

Kill it at any point and re-run the same command: ``Run.resume``
restores params, optimizer, znorm cache, budget statistics AND the
adaptive controller's band state from the last durable checkpoint, so
the budget trajectory continues instead of resetting.  ``--adaptive``
attaches an ESSProportional budget controller to the MLP blocks; the
run report prints its trajectory.  ``--full-size`` trains the ~125M
published xLSTM config.
"""
import argparse
import dataclasses

from repro.api import DataSpec, Run, RunSpec
from repro.core import (BudgetSchedule, ESSProportional, PolicyRules,
                        Rule, WTACRSConfig)
from repro.core.config import EstimatorKind, NormSource
from repro.core.lora import LoRAConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models import common as cm
from repro.train import optim


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="xlstm-125m")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir", default="/tmp/wtacrs_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--budget", type=float, default=0.3)
    ap.add_argument("--warmup-exact", type=int, default=0,
                    help="steps to run every sampled layer exact before "
                         "dropping to --budget (BudgetSchedule)")
    ap.add_argument("--adaptive", action="store_true",
                    help="ESSProportional budget controller on the MLPs")
    ap.add_argument("--full-size", action="store_true")
    args = ap.parse_args()
    enable_compile_cache()

    # CACHED_GRAD: the dataset gradient-norm cache actually drives the
    # column-row probabilities — RunSpec sees it and wires the cache,
    # sample_ids plumbing, and (for --adaptive) budget_stats by itself.
    base = WTACRSConfig(kind=EstimatorKind.WTA_CRS, budget=args.budget,
                        min_rows=4, norm_source=NormSource.CACHED_GRAD)
    rules = None
    if args.adaptive:
        rules = PolicyRules.of(Rule.of(
            "*mlp*", base,
            ESSProportional(b_min=0.1, b_max=0.6, levels=6, warmup=3)))
    elif args.warmup_exact > 0:
        # MoE routers and expert-share linears sample flattened rows:
        # the per-sample gradient-norm cache has no column for them
        # (PT003), so they take activation norms while everything else
        # uses the cache.
        rows = dataclasses.replace(
            base, norm_source=NormSource.ACTIVATION_ONLY)
        rules = PolicyRules.of(
            ("*moe_*", rows),
            ("*", base, BudgetSchedule.warmup_exact(
                begin_step=args.warmup_exact, end=args.budget)))
    policy = cm.Policy(
        wtacrs=base, rules=rules,
        lora=LoRAConfig(rank=16, enabled=False),  # LoRA params are module-
        # level in this framework; flip enabled=True for adapter training
    )

    spec = RunSpec(
        arch=args.arch, reduced=not args.full_size, policy=policy,
        steps=args.steps, batch_size=args.batch,
        optimizer=optim.AdamWConfig(weight_decay=0.0, grad_clip_norm=1.0),
        lr=3e-3, lr_schedule="wsd", warmup=10,
        data=DataSpec(seq_len=args.seq, n_samples=512, branching=2),
        checkpoint_dir=args.ckpt_dir, checkpoint_every=args.ckpt_every)

    run = Run.resume(spec)
    if run.state is not None:
        print(f"resumed from step {int(run.state['step'])}")
    print(f"{len(run.tags)} WTA-CRS'd linears; dataset cache over "
          f"{spec.data.n_samples} samples")
    run.fit(log_every=10)
    run.save()
    print(run.report())
    print("final checkpoint written; re-run to verify resume is a no-op")


if __name__ == "__main__":
    main()
