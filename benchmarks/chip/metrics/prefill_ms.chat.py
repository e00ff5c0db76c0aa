"""Device time of admission prefill per request admitted in the traced
window (the full-depth prefill program, chunked by prompt length)."""


def read(ctx):
    s, n = ctx["trace"].modules_matching("jit_pf")
    admitted = len(ctx["counts"]["prompt_lens"])
    return 1e3 * s / admitted if n and admitted else None
