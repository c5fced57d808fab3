"""Chip smoke test: four-chip sharded training on a TPU host.

    python chip_smoke.py

Full-width qwen2-1.5b training through ``launch/train.py`` (``--profile
fsdp``): 4 steps on the searched mesh (``--topology-aware``) against the
same 4 steps on the identity mesh; the losses must agree. This is the one
path the chip benchmark (``benchmarks/chip/``) has no cell for. A one-chip
check of the engine or of serving is a benchmark cell:
``python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``.

The phase prints one result line; a failed check raises, so the exit code
is nonzero. Wall seconds include compilation and host work: they are
set-up-inclusive, not device times. The last line printed is the JSON
device record. The script stops before the phase when JAX finds no TPU or
fewer than four devices.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

CHIPS = 4
# searched vs identity mesh losses: same program, device order permuted;
# only collective reduction order may differ.
LOSS_RTOL = 1e-3


def train_phase(*, smoke: bool = False, seq: int = 512):
    """4 fsdp training steps at batch 8 on the searched mesh against the
    identity mesh, through ``launch/train.py``; per-device peak memory
    after both."""
    import jax

    from repro.launch import train
    steps, batch = 4, 8
    argv = ["--arch", "qwen2-1.5b", "--profile", "fsdp", "--steps",
            str(steps), "--batch", str(batch), "--seq", str(seq)]
    argv += ["--smoke"] if smoke else []
    t0 = time.time()
    searched = train.main(argv + ["--topology-aware"])
    t1 = time.time()
    identity = train.main(argv)
    t2 = time.time()
    a, b = np.asarray(searched.losses), np.asarray(identity.losses)
    rel = float(np.max(np.abs(a - b) / np.abs(b)))
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()]
    print(f"[train] qwen2-1.5b fsdp devices={len(jax.devices())} "
          f"batch={batch} seq={seq} losses searched={a.tolist()} "
          f"identity={b.tolist()} max rel diff={rel:.3e} "
          f"(bound {LOSS_RTOL}) peak_bytes_in_use per device={peaks} | "
          f"wall s (set-up-inclusive): searched={t1 - t0:.3f} "
          f"identity={t2 - t1:.3f}", flush=True)
    if not (rel <= LOSS_RTOL and np.isfinite(a).all()):
        raise AssertionError(f"searched vs identity losses differ by "
                             f"{rel:.3e} > {LOSS_RTOL}")
    return dict(searched=a.tolist(), identity=b.tolist(), rel=rel,
                peak_bytes_in_use=peaks)


def main(argv=None) -> int:
    argparse.ArgumentParser(description=__doc__.split("\n")[0]).parse_args(
        argv)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 1
    if len(devices) < CHIPS:
        print(f"chip_smoke: needs {CHIPS} chips but JAX sees "
              f"{len(devices)} device(s); nothing was run", file=sys.stderr)
        return 1
    from repro.kernels import ops
    from repro.launch import compile_cache
    compile_cache.enable()
    if not ops.use_pallas():
        raise AssertionError("Pallas kernels are off on a TPU")
    train_phase()
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
