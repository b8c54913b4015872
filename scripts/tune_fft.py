"""Tune the planar FFT (VERDICT r3 #3): sweep matmul precision and the
four-step radix cutoff on the attached chip, validating accuracy against
numpy at 128^3 before timing 512^3.

Each config runs in a subprocess (the cutoff is an import-time
constant); the parent never touches JAX, so each child can own the
chip in turn.  Prints one JSON line per config.

    python scripts/tune_fft.py            # full sweep
"""

import json
import os
import subprocess
import sys

WORKER = r"""
import json, os, sys, time
import numpy as np
import jax, jax.numpy as jnp

sys.path.insert(0, os.environ["REPO"])
import heat_tpu as ht

prec = os.environ["HEAT_TPU_FFT_PRECISION"]
cut = os.environ["HEAT_TPU_FFT_CUTOFF"]

# accuracy gate at 128^3 vs numpy (planar path forced)
os.environ["HEAT_TPU_PLANAR"] = "1"
rng = np.random.default_rng(0)
xa = rng.standard_normal((128, 128, 128)).astype(np.float32)
fa = ht.fft.fftn(ht.array(xa))
re, im = fa._planar
got = np.asarray(re) + 1j * np.asarray(im)
want = np.fft.fftn(xa)
rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))

# timing at 512^3: the window must DOMINATE the link's per-program
# dispatch floor (~0.09 s observed in some sessions) — an undersized
# window reads ~2x slower than the device truth (r4 lesson; see
# bench._time_amortized's floor-ratio growth)
s = 512
x = ht.random.randn(s, s, s, split=0).astype(ht.float32)
float(x.sum())
def fft():
    return ht.fft.fftn(x)
r = fft()
rre, rim = r._planar
float(rre[0, 0, 0])  # compile + drain
f0 = jax.jit(lambda v: v + 1.0)
z = jnp.zeros(())
float(f0(z))
floor = float("inf")
for _ in range(5):
    t0 = time.perf_counter()
    float(f0(z))
    floor = min(floor, time.perf_counter() - t0)
n_iter = 32
best = float("inf")
for _ in range(3):
    t0 = time.perf_counter()
    out = None
    for _ in range(n_iter):
        out = fft()
    orr, ori = out._planar
    float(orr[0, 0, 0])
    best = min(best, (time.perf_counter() - t0 - floor) / n_iter)
n = s ** 3
print(json.dumps({
    "precision": prec, "cutoff": int(cut), "rel_err_128": rel,
    "sec_per_fft3d_512": round(best, 4),
    "nominal_gflops": round(5.0 * n * np.log2(n) / best / 1e9, 1),
}))
"""


def main():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for prec in ("highest", "high", "default"):
        for cut in ("32", "64", "128"):
            env = dict(os.environ)
            env.update(
                REPO=repo,
                HEAT_TPU_FFT_PRECISION=prec,
                HEAT_TPU_FFT_CUTOFF=cut,
            )
            r = subprocess.run(
                [sys.executable, "-c", WORKER], env=env, capture_output=True,
                text=True, timeout=1800,
            )
            line = (r.stdout.strip().splitlines() or ["{}"])[-1]
            if r.returncode != 0:
                line = json.dumps({
                    "precision": prec, "cutoff": int(cut),
                    "error": r.stderr.strip()[-300:],
                })
            print(line, flush=True)


if __name__ == "__main__":
    main()
