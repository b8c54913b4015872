"""The rate a chip's transposes achieved: the bytes a chip must send for
them a solve (``work["ici_bytes"]``, from the shapes and the stated dtypes)
times the traced solves, over the seconds ``fft_alltoall_ms`` reads.  An
achieved rate, not a share of a peak: peaks.json holds no interconnect peak."""

from chipbench.run import load_py


def read(run):
    s = load_py("layer_metrics", "fft_alltoall_ms").seconds_a_chip(run, "fft_alltoall_gbps")
    if s is None or "ici_bytes" not in run["work"]:
        return None
    return run["work"]["ici_bytes"] * run["solves"] / s / 1e9
