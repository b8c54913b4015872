"""Device time a fit of everything that is no kernel: the device's busy time
(``trace["busy_s"]`` over the solves) less the self times of the fit's two
kernels, ``gram_syrk`` and ``lasso_cd``.  That is the moments' loop, the
table's second read (6.92 of 7.3 ms a fit: chip run, PERF.md, PR 39), and the
dozen small operations around the kernels; moments taken inside the Gram's
pass would bring it under a millisecond.  Without both kernels among the
trace's top operations a remainder would be a guess: nothing, and the
reason."""

from chipbench.run import load_py


def read(run):
    seconds = load_py("layer_metrics", "lasso_gram_ms").seconds
    kernels = [seconds(run, "lasso_xla_ms", kernel=k) for k in ("gram_syrk", "lasso_cd")]
    if None in kernels or not run["trace"]["busy_s"]:
        return None
    return 1000.0 * (run["trace"]["busy_s"] - sum(kernels)) / run["solves"]
