"""The paper, end to end: recommend a configuration for a production cell.

    PYTHONPATH=src python examples/tune_sapphire.py \
        [--arch yi-6b] [--shape train_4k] [--top-k 16] [--quick]

Pipeline (paper Fig. 3): raw knob space -> §3.2 constraint resolution ->
§3.3 Lasso ranking (~300 noisy test-cluster evaluations) -> §3.4 GP-BO
with dynamic boundaries over the top-K -> report vs default & expert
manual configs.  Prints the Table-2-style top-knob list and the
recommended config diff.
"""

import argparse
import json

from repro.compile_cache import use_compile_cache
from repro.core.strategy import BOConfig, strategy_names
from repro.core.tuner import Sapphire


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--top-k", type=int, default=16)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--batch", type=int, default=8,
                    help="configs per Experiment-Unit round (q-batch BO + "
                         "chunked ranking); 1 = the paper's sequential loop")
    ap.add_argument("--strategy", default="bo", choices=strategy_names(),
                    help="search-stage strategy from the registry")
    ap.add_argument("--async-eval", action="store_true",
                    help="drive rank/search through the overlapped "
                         "Controller.run_async loop (identical results on "
                         "the analytic test cluster; a wall-clock win on "
                         "services that stream completions out of order)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    s = Sapphire(
        arch=args.arch, shape=args.shape, top_k=args.top_k,
        multi_pod=args.multi_pod,
        n_rank_samples=120 if args.quick else 300,
        batch_size=args.batch,
        strategy=args.strategy,
        bo_config=BOConfig(n_init=8, n_iter=16 if args.quick else 48,
                           n_candidates=1024, fit_steps=100, seed=args.seed),
        seed=args.seed, async_eval=args.async_eval)
    res = s.tune()

    print("\n=== SAPPHIRE recommendation ===")
    print(json.dumps(res.summary(), indent=1, default=str))
    print("\ntop knobs (Table-2 style):")
    for r in res.ranking.table(args.top_k):
        print(f"  {r['knob']:28s} {r['type']:11s} default={r['default']!s:>8s}"
              f" range={r['range']:20s} imp={r['importance']:.4f}")
    print("\nrecommended config (non-default knobs only):")
    defaults = res.ranking.space.default_config()
    diff = {k: v for k, v in res.best_config.items()
            if defaults.get(k) != v}
    print(json.dumps(diff, indent=1, default=str))
    print(f"\nspeedup vs default: {res.speedup_vs_default:.2f}x | "
          f"vs expert manual: {res.speedup_vs_expert:.2f}x")


if __name__ == "__main__":
    main()
