"""Installation smoke test (analog of scripts/heat_test.py).

The reference's smoke test builds ``ht.arange(10, split=0)`` under mpirun
and prints the local chunk and the global array on every rank.  The mesh
analog: build the same split array over whatever devices JAX finds (the
attached TPU chip(s), or the CPU), print each device's shard and the
global result.  One process owns the chip; for the full on-chip check
run ``python chip_smoke.py`` instead.

    python scripts/heat_test.py                      # the devices JAX finds
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python scripts/heat_test.py                  # virtual 8-device mesh
"""

import os
import sys

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..")))

import heat_tpu as ht


def main() -> None:
    from heat_tpu.core.compile_cache import use_compile_cache

    use_compile_cache()
    comm = ht.get_comm()
    print(f"mesh: {comm.size} device(s): {[str(d) for d in comm.devices]}")

    x = ht.arange(10, split=0)
    for rank in range(comm.size):
        _, _, slices = comm.chunk((10,), 0, rank=rank)
        print(f"rank {rank}: local shard {x.numpy()[slices].tolist()}")
    print(f"global: {x.numpy().tolist()}")
    assert float(x.sum()) == 45.0
    print("smoke test OK")


if __name__ == "__main__":
    main()
