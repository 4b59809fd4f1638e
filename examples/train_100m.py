"""End-to-end driver: train a ~100M-parameter LM for a few hundred steps
with the full production stack — the same build_train_step the 256-chip
dry-run lowers, plus local-update (FL-style) outer sync, checkpointing,
and crash-resume.

  PYTHONPATH=src python examples/train_100m.py [--steps 300]

The config is a 100M-scale qwen3-family model (12L, d=512), trained on the
synthetic Markov token stream; loss should fall from ~ln(V) toward the
stream's conditional entropy.
"""

import argparse
import shutil
import tempfile

from repro.configs import get_reduced
from repro.launch.train import train
from repro.utils.compile_cache import enable_compile_cache


def lm_100m_cfg():
    cfg = get_reduced("qwen3-8b").replace(
        name="qwen3-100m",
        n_layers=12,
        d_model=512,
        n_heads=8,
        n_kv_heads=4,
        d_ff=1536,
        vocab_size=4096,
        loss_chunk=0,
    )
    n = cfg.param_count()
    print(f"[train_100m] model: {cfg.name}, {n/1e6:.1f}M params")
    return cfg


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    args = ap.parse_args()

    cfg = lm_100m_cfg()
    # register the custom config by monkey-dropping into train()'s path:
    import repro.launch.train as T

    orig_get_reduced = T.get_reduced
    T.get_reduced = lambda arch: cfg if arch == "qwen3-100m" else orig_get_reduced(arch)

    ckpt = tempfile.mkdtemp(prefix="edgefl_100m_")
    try:
        out = train(
            "qwen3-100m",
            reduced=True,
            steps=args.steps,
            inner_steps=10,  # local-update outer sync every 10 steps
            batch=args.batch,
            seq=args.seq,
            ckpt_dir=ckpt,
            ckpt_every=50,
            log_every=20,
        )
        first = out["losses"][0]
        last = out["final_loss"]
        print(f"[train_100m] loss {first:.3f} -> {last:.3f} over {args.steps} steps")
        assert last < first - 0.5, "loss must fall substantially"
        print("[train_100m] OK")
    finally:
        T.get_reduced = orig_get_reduced
        shutil.rmtree(ckpt, ignore_errors=True)


if __name__ == "__main__":
    enable_compile_cache()
    main()
