"""FFT continuous benchmarks: pencil-decomposed split-axis transforms.

The reference has no FFT cb suite; this one tracks the pencil on the shapes
the 3-D FFT baseline config uses.  Since PR 31 every transform of a split
array is ONE ``shard_map`` program: along the split axis ``all_to_all``, the
transform, ``all_to_all`` back; along the other axes XLA's ``fft`` on each
device's own slab (GSPMD gathers a sharded array for it on some backends).
``fftn_pencil`` at 1024^3 over four chips is the chip benchmark's cell
``fftn-pencil-1024.4chip`` (``chipbench/``, PERF.md)."""

# flake8: noqa
import heat_tpu as ht
from monitor import monitor


@monitor()
def fft_split_axis(volume):
    return ht.fft.fft(volume, axis=0)


@monitor()
def fftn_pencil(volume):
    return ht.fft.fftn(volume)


@monitor()
def fft_roundtrip(volume):
    return ht.fft.ifftn(ht.fft.fftn(volume))


def run_fft_benchmarks(scale: float = 1.0):
    s = max(int(128 * scale), 16)
    p = ht.get_comm().size
    s = -(-s // p) * p  # divisible partner extents for the pencil path
    vol = ht.random.randn(s, s, s, split=0).astype(ht.float32)
    fft_split_axis(vol)
    fftn_pencil(vol)
    fft_roundtrip(vol)
