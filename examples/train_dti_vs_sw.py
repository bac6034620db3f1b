"""End-to-end driver: sliding-window vs DTI on one dataset, full runtime.

    PYTHONPATH=src python examples/train_dti_vs_sw.py [--k 10] [--epochs 2]

Runs the training CLI's ``repro.launch.train.run_lm`` — checkpointing,
straggler monitor, cosine schedule, full evaluation — for both paradigms
back to back, each for ``--epochs`` passes over its own training rows.
That is the paper's protocol: SW trains one prompt per target, DTI one per
k targets, so at equal epochs the ratio of steady-state training time is
the paper's headline reduction.
"""
import argparse

from repro.configs import get_arch
from repro.core.dti import pack_prompts, train_max_len
from repro.data.synthetic import make_ctr_dataset, split_users
from repro.launch.train import build_parser, build_prompt_sets, run_lm


def epoch_rows(args) -> int:
    """Training rows in one pass over ``args.paradigm``'s prompt set, as
    ``run_lm`` builds them."""
    cfg = get_arch(args.arch).smoke
    ds = make_ctr_dataset(n_users=args.users, n_items=args.items,
                          seq_len=args.seq, vocab_size=cfg.vocab_size,
                          seed=args.seed)
    max_len = train_max_len(args.n_ctx,
                            1 if args.paradigm == "sw" else args.k,
                            ds.avg_item_tokens)
    prompts = build_prompt_sets(ds, split_users(ds), paradigm=args.paradigm,
                                n_ctx=args.n_ctx, k=args.k,
                                max_len=max_len)[0]
    return len(pack_prompts(prompts, max_len) if args.pack else prompts)


def run(paradigm: str, *, k: int, epochs: float, pack: bool,
        attn_impl) -> dict:
    argv = ["--paradigm", paradigm, "--k", str(k)]
    argv += ["--pack"] if pack else []
    argv += ["--attn-impl", attn_impl] if attn_impl else []
    args = build_parser().parse_args(argv)
    args.steps = max(2, int(round(epochs * epoch_rows(args) / args.batch)))
    result = run_lm(args)
    # compile excluded: the first step's XLA compile is not training time
    result["epoch_time_s"] = result["steady_step_s"] * result["steps"]
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--epochs", type=float, default=1.0)
    ap.add_argument("--no-pack", action="store_true",
                    help="disable segment-aware prompt packing for DTI")
    ap.add_argument("--attn-impl", default=None, dest="attn_impl",
                    choices=["dense", "blocked", "pallas"],
                    help="attention path for both paradigms; 'pallas' "
                         "trains through the fused kernel's custom VJP "
                         "(interpret mode off-TPU, no blocked fallback)")
    args = ap.parse_args()
    pack = not args.no_pack

    # pack both paradigms (or neither) so the headline reduction compares
    # SW vs DTI like-for-like, not packing vs no-packing
    impl_note = f", attn={args.attn_impl}" if args.attn_impl else ""
    layout = "packed" if pack else "unpacked"
    print(f"== sliding-window baseline ({args.epochs} epochs, "
          f"{layout}{impl_note}) ==")
    sw = run("sw", k=args.k, epochs=args.epochs, pack=pack,
             attn_impl=args.attn_impl)
    print(f"   time {sw['epoch_time_s']:.1f}s  AUC {sw['auc']:.4f} "
          f"LogLoss {sw['log_loss']:.4f}  pad {sw['pad_fraction']:.1%}")

    print(f"== DTI k={args.k} ({args.epochs} epochs, {layout}{impl_note}) ==")
    dti = run("dti", k=args.k, epochs=args.epochs, pack=pack,
              attn_impl=args.attn_impl)
    print(f"   time {dti['epoch_time_s']:.1f}s  AUC {dti['auc']:.4f} "
          f"LogLoss {dti['log_loss']:.4f}  pad {dti['pad_fraction']:.1%}  "
          f"steady {dti['steady_tokens_per_s']:.0f} tok/s")

    red = (1 - dti["epoch_time_s"] / sw["epoch_time_s"]) * 100
    print(f"\nDTI trained in {dti['epoch_time_s']:.1f}s vs SW "
          f"{sw['epoch_time_s']:.1f}s  ->  {red:.1f}% reduction "
          f"(paper: ~80-92% for k=10..50), "
          f"dAUC = {dti['auc'] - sw['auc']:+.4f}")


if __name__ == "__main__":
    main()
