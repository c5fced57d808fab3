#!/usr/bin/env bash
# Tier-1 gate — the exact command CI runs (.github/workflows/ci.yml).
# Usage: scripts/ci.sh [extra pytest args]
#        BENCH_SMOKE=1 scripts/ci.sh   # additionally run the launcher
#                                      # smokes: quickstart, dry-run,
#                                      # serving, chaos serving and
#                                      # training, embed, device V-cycle.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH}

echo "== ruff lint =="
if command -v ruff >/dev/null 2>&1; then
  ruff check src tests benchmarks scripts examples
else
  echo "ruff not installed; skipping (CI installs it)"
fi

echo "== static analysis gate (repro.analysis) =="
# kernel race/tiling verifier + sharding lint; error findings fail the
# gate, the JSON goes up as a CI artifact
python -m repro.analysis --severity error --json analysis_findings.json

python -m pytest -x -q "$@"

if [[ "${BENCH_SMOKE:-0}" == "1" ]]; then
  echo "== quickstart example =="
  python examples/quickstart.py
  echo "== machine-preset dryrun smoke (gpu-superpod, topology-aware) =="
  # a tiny cell on a non-default machine preset: presets can't silently rot
  python -m repro.launch.dryrun --arch qwen2-1.5b --shape train_4k \
    --machine gpu-superpod --topology-aware \
    --override n_layers=1 --override batch=2 --override seq=8
  echo "== serving smoke (continuous batching + page placement) =="
  # a tiny stream through the real engine: FIFO admission, paged decode,
  # one drift-placement epoch — end-to-end, not just the unit tests
  python -m repro.launch.serve --arch qwen2-1.5b --smoke --stream \
    --num-requests 8 --prompt-len 8 --gen-len 8 --slots 4 --page-size 4 \
    --replace-every 8 --place-devices 4 --seed 0
  echo "== chaos serving smoke (leaf death mid-stream) =="
  # same stream, one injected device death: every request must still
  # complete and survivor tokens must be bit-identical to the clean run
  # (DESIGN.md §Fault-tolerance replay determinism)
  python -m repro.launch.serve --arch qwen2-1.5b --smoke --stream \
    --num-requests 8 --prompt-len 8 --gen-len 8 --slots 4 --page-size 4 \
    --replace-every 8 --place-devices 4 --seed 0 \
    --trace serve_trace_clean.json
  python -m repro.launch.serve --arch qwen2-1.5b --smoke --stream \
    --num-requests 8 --prompt-len 8 --gen-len 8 --slots 4 --page-size 4 \
    --replace-every 8 --place-devices 4 --seed 0 \
    --fault-plan "6:leaf_death:1" --trace serve_trace_chaos.json
  python - <<'PYEOF'
import json
clean = json.load(open("serve_trace_clean.json"))
chaos = json.load(open("serve_trace_chaos.json"))
assert not chaos["failed"], f"chaos run failed requests: {chaos['failed']}"
assert len(chaos["requests"]) == len(clean["requests"])
cg = {r["rid"]: r["generated"] for r in clean["requests"]}
for r in chaos["requests"]:
    assert r["generated"] == cg[r["rid"]], \
        f"rid {r['rid']}: tokens diverged after injected leaf death"
assert chaos["recoveries"], "fault plan injected but no recovery recorded"
print(f"[CI] chaos serving OK: {len(chaos['requests'])} requests "
      f"bit-identical to clean, "
      f"{chaos['requests_retried']} retried, "
      f"{chaos['tokens_reprefilled']} tokens re-prefilled")
PYEOF
  echo "== chaos training smoke (supervised restart + ckpt restore) =="
  ckpt_dir="$(mktemp -d)"
  python -m repro.launch.train --arch qwen2-1.5b --smoke --steps 12 \
    --batch 2 --seq 16 --ckpt-dir "$ckpt_dir" --ckpt-every 4 \
    --fault-plan "7:leaf_death:1" | tee /dev/stderr | \
    grep -q "attempts=2" || { echo "supervised restart did not run"; exit 1; }
  rm -rf "$ckpt_dir"
  echo "== embed-sharded training smoke (repro.embed end-to-end) =="
  # recsys cell with the full embed subsystem on: co-access probe ->
  # partitioned item table on the heterogeneous preset -> sparse table
  # updates -> hot-row-cache traffic report -> prefetched batch stream;
  # the launcher prints the traffic comparison, the grep pins that the
  # prefetcher genuinely ran ahead of the consumer
  python -m repro.launch.train --arch two-tower-retrieval --smoke \
    --steps 6 --batch 8 --embed-shard --embed-cache-rows 64 \
    --prefetch 2 --embed-machine tpu-mixed-32 | tee /dev/stderr | \
    grep -q "max_occupancy=[1-9]" || \
    { echo "embed smoke: prefetcher never overlapped"; exit 1; }
  echo "== device V-cycle smoke (partition backend=device + sparse map) =="
  # the device front end end-to-end: jitted coarsening + capacity-prefix
  # initial through partition(), verified against the path-walking
  # oracle, then mapped onto the torus-2d machine through the sparse
  # routing oracle (DESIGN.md §Device-V-cycle)
  python - <<'PYEOF'
import numpy as np
from repro.core import mapping, objective
from repro.core.machine import resolve
from repro.core.partitioner import PartitionConfig, partition, verify
from repro.core.topology import balanced_tree
from repro.graph.generators import rmat
import jax.numpy as jnp

g = rmat(600, 2400, seed=0)
topo = balanced_tree((4, 4, 4))                 # k=64 = the 8x8 torus
res = partition(g, topo, PartitionConfig(seed=0, backend="device"))
verify(g, topo, res)
W = np.array(objective.quotient_matrix(
    jnp.asarray(res.part, dtype=jnp.int32), jnp.asarray(g.senders),
    jnp.asarray(g.receivers), jnp.asarray(g.edge_weight), topo.k))
np.fill_diagonal(W, 0.0)
mtopo = resolve("torus-2d").topology()
m = mapping.search((8, 8), mtopo, W, n_random=2, seed=0)
ident = mapping.makespan_of_device_map(W, mtopo, np.arange(mtopo.k))
assert m.bottleneck <= ident + 1e-6, (m.bottleneck, ident)
print(f"[CI] device V-cycle OK: makespan={res.makespan:.1f}, "
      f"mapped bottleneck={m.bottleneck:.2f} (identity {ident:.2f})")
PYEOF
fi
