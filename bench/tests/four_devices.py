"""Child process of ``test_bench_faults.py``: granite-34b on the Hecaton 2x2
traffic (`bench/traffic/hecaton2x2_4k.json`, fused ring overlap), at the
smoke widths on four virtual CPU devices (``XLA_FLAGS`` set by the parent),
held to the one-chip cell's limits, once as it is and once with every ring
hop between devices left out (``jax.lax.ppermute`` returns the local
shard).  Prints one JSON line."""

import json
import sys
import time
from pathlib import Path

sys.path[:0] = [str(Path(__file__).resolve().parents[2]),
                str(Path(__file__).resolve().parents[2] / "src")]

import jax  # noqa: E402

from bench import train_cell  # noqa: E402
from bench.tests.tiny import patched_registry, tiny_cell  # noqa: E402

CELL = "granite-34b.hecaton2x2_4k"


def run():
    cell, smoke = tiny_cell(CELL, limits_of="qwen3-0.6b.train_4k")
    with patched_registry(smoke):
        return train_cell.run(cell, 2**31 + 4099, 0.3, False,
                              jax.devices()[:4], time.perf_counter())


def main():
    assert len(jax.devices()) == 4, jax.devices()
    sound = run()
    real = jax.lax.ppermute
    jax.lax.ppermute = lambda x, axis_name, perm: x
    try:
        broken = run()
    finally:
        jax.lax.ppermute = real
    print(json.dumps({"sound": sound, "no_exchange": broken}))


if __name__ == "__main__":
    main()
