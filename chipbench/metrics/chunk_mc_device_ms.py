"""Device milliseconds per run of the fused Monte-Carlo chunk program
(``dse/evaluate.py`` ``_chunk_mc_impl``, XLA module
``jit__chunk_mc_impl``) in the traced window.  Nothing where that
program did not run."""
from harness.reduce import module_ms_per_call


def read(ctx):
    return module_ms_per_call(ctx["trace"], "_chunk_mc_impl")
