"""LM-side smoke: pretrain a reduced assigned-architecture config with
the full substrate (synthetic pipeline, AdamW, checkpoints, resume).

  PYTHONPATH=src python examples/lm_pretrain_smoke.py [arch]

This is the CPU-runnable template of the pod-scale flow that the
multi-pod dry-run compiles at (16,16) and (2,16,16); see
src/repro/launch/train.py for the full driver (crash/resume, int8
gradient compression).
"""
import os
import sys
import tempfile

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.engine import enable_compile_cache  # noqa: E402
from repro.launch.train import main as train_main  # noqa: E402

if __name__ == "__main__":
    enable_compile_cache()
    arch = sys.argv[1] if len(sys.argv) > 1 else "xlstm-125m"
    ckpt = tempfile.mkdtemp(prefix="repro_ckpt_")
    sys.exit(train_main([
        "--arch", arch, "--smoke", "--steps", "60", "--batch", "8",
        "--seq", "128", "--lr", "1e-3", "--log-every", "10",
        "--ckpt-dir", ckpt, "--ckpt-every", "30", "--resume", "auto",
    ]))
