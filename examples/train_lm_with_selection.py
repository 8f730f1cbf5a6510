"""End-to-end driver: train an LM with coreset-selected batches routed
through the selection stack (``select(algo, CoresetObjective, ...)``),
with checkpoint/restart fault tolerance.

    PYTHONPATH=src python examples/train_lm_with_selection.py \
        [--arch smollm-135m] [--steps 300] [--algo dash] [--no-selection]

Any registry algorithm is a one-string swap (--algo dash | greedy |
lazy_greedy | stochastic_greedy | topk | random).  Uses the reduced
config of the chosen arch so it runs on CPU; the same loop lowers
unchanged on the production mesh (see repro/launch/dryrun.py).
``--assert-improves`` exits nonzero unless the loss decreased — the CI
training-smoke contract.
"""

import argparse
import logging

import numpy as np

from repro.configs import TrainConfig, get_reduced_config
from repro.data.pipeline import TokenPipeline
from repro.data.selection import BatchSelector
from repro.data.synthetic import make_lm_tokens
from repro.models import build_model
from repro.train.loop import train_loop
from repro.utils.compile_cache import enable_compile_cache

logging.basicConfig(level=logging.INFO, format="%(message)s")


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--algo", default="dash",
                    help="any core.algorithms registry name")
    ap.add_argument("--feature-mode", default="grad",
                    choices=["embed", "hidden", "grad"])
    ap.add_argument("--selection-every", type=int, default=2)
    ap.add_argument("--pool-factor", type=int, default=4)
    ap.add_argument("--no-selection", action="store_true")
    ap.add_argument("--assert-improves", action="store_true",
                    help="fail unless the tail loss beats the head loss")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    args = ap.parse_args()

    cfg = get_reduced_config(args.arch)
    model = build_model(cfg)
    tokens = make_lm_tokens(0, 2_000_000, cfg.vocab_size)

    tcfg = TrainConfig(total_steps=args.steps, learning_rate=3e-3,
                       warmup_steps=min(20, max(args.steps // 10, 1)),
                       checkpoint_every=100)
    if args.no_selection:
        selector = None
    else:
        opts = {"n_samples": 4} if args.algo == "dash" else {}
        selector = BatchSelector(k=args.batch, algo=args.algo,
                                 feature_mode=args.feature_mode,
                                 embed_dim_cap=32, **opts)

    with TokenPipeline(tokens, args.batch, args.seq) as pipeline:
        result = train_loop(model, tcfg, pipeline, ckpt_dir=args.ckpt_dir,
                            selector=selector,
                            selection_every=args.selection_every,
                            selection_pool_factor=args.pool_factor,
                            log_every=25)

    head = float(np.mean(result.losses[:5]))
    tail = float(np.mean(result.losses[-5:]))
    print(f"ran {result.steps_run} steps; loss {head:.3f} → {tail:.3f} "
          f"(restarts: {result.restarts}, "
          f"selection {result.selection_time_s:.1f}s, "
          f"{len(result.selections)} selection periods)")
    if args.assert_improves:
        assert tail < head, f"loss did not improve: {head:.3f} → {tail:.3f}"


if __name__ == "__main__":
    main()
