"""The comparison that decides ``correct``: served greedy tokens against the
plain reference, teacher-forced on each sampled request's own prompt and
served tokens.

Every served token is judged by the head that should have produced it.
Token 0 comes from the full-depth prefill (final head at the last prompt
position). Token t >= 1 was decided at position S + t - 1: the reference's
exit-head confidence there says whether it exits (exit head) or goes on to
stage 2 (final head). Within ``band`` of the threshold, in log confidence,
bf16 rounding may flip the program's decision either way, so both heads are
admissible there and the served token says which one was taken: the exit
head if it is that head's greedy token, else the final head. That choice
also sets which positions hold stage-2 keys and values, so the reference
follows the program's own path through the ambiguous tokens.

The number compared is the widest gap by which a served token's reference
logit lies below the reference's best logit of the head that judges it.
Beside it, the widest distance from the threshold, in log confidence, of
a flipped decision (a served token that is the greedy token of the head
the reference's decision does not pick, and not of the one it picks) is
compared with the band itself.
All shapes are padded to the pool's ``max_len`` and the mix's longest
output, so one compiled reference serves every request.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Callable, List

import numpy as np

import jax
import jax.numpy as jnp


@dataclass
class Judged:
    max_gap: float
    n_tokens: int
    n_ambiguous: int
    n_hard: int
    worst_at: int           # the token of the widest gap
    worst_lc: float         # its log exit confidence less log threshold
    n_flips: int            # the other head's token: a flipped decision
    flip_lc: float          # the largest |log confidence| of those


class Reference:
    """The reference's two stages, jitted at padded shapes."""

    def __init__(self, ref_mod, a: dict, w: dict, max_len: int,
                 max_out: int, matmul: Callable = None):
        self.a, self.w = a, w
        self.max_len, self.max_out = max_len, max_out
        mm = matmul or ref_mod.f32_matmul
        self._s1 = jax.jit(functools.partial(ref_mod.stage1, a, matmul=mm))
        self._s2 = jax.jit(functools.partial(ref_mod.stage2, a, matmul=mm))

    def logits(self, prompt: np.ndarray, served: List[int], decide):
        """Exit and final logits (max_out, V) at the decision positions of
        tokens 0..n-1 (row t judges served token t; row 0's exit logits are
        unused). ``decide(conf (n,), exit_argmax (n,)) -> hard (n,) bool``
        picks the stage-2 path from the exit-head results."""
        S, n = len(prompt), len(served)
        toks = np.zeros(self.max_len, np.int32)
        toks[:S] = prompt
        toks[S:S + n - 1] = served[:-1]
        rows = np.full(self.max_out, S - 1, np.int32)
        rows[:n] = S - 1 + np.arange(n)
        h, ex = self._s1(self.w, jnp.asarray(toks), jnp.asarray(rows))
        conf, arg = _conf_argmax(ex)
        conf, arg = np.asarray(conf)[:n], np.asarray(arg)[:n]
        hard = np.asarray(decide(conf, arg), bool)
        hard[0] = True                      # token 0: full-depth prefill
        keep = np.ones(self.max_len, bool)
        keep[rows[1:n]] = hard[1:]
        fin = self._s2(self.w, h, jnp.asarray(keep), jnp.asarray(rows))
        return ex, fin, conf, arg, hard


@jax.jit
def _conf_argmax(logits):
    m = jnp.max(logits, -1)
    return 1.0 / jnp.sum(jnp.exp(logits - m[:, None]), -1), \
        jnp.argmax(logits, -1)


@jax.jit
def _gap(logits, tok):
    return jnp.max(logits, -1) - jnp.take_along_axis(
        logits, tok[:, None], -1)[:, 0]


def judge(ref: Reference, prompt, served: List[int], c_thr: float,
          band: float, pick=None) -> Judged:
    """Judge ``pick`` (one candidate token per served position; the served
    tokens themselves by default) on the reference teacher-forced with the
    served stream."""
    served = np.asarray([int(x) for x in served], np.int32)
    pick = served if pick is None else np.asarray(pick, np.int32)
    n = len(served)
    lthr = np.log(c_thr)

    def decide(conf, arg):
        lc = np.log(np.maximum(conf, 1e-38)) - lthr
        return np.where(np.abs(lc) <= band, arg != served, lc <= 0)

    ex, fin, conf, arg, hard = ref.logits(np.asarray(prompt), served, decide)
    amb = np.abs(np.log(np.maximum(conf, 1e-38)) - lthr) <= band
    amb[0] = False                      # token 0 has no exit decision
    tok = np.zeros(ref.max_out, np.int32)
    tok[:n] = pick
    g_ex = np.asarray(_gap(ex, jnp.asarray(tok)))[:n]
    g_fin = np.asarray(_gap(fin, jnp.asarray(tok)))[:n]
    # inside the band a token may come from either head
    gaps = np.where(amb, np.minimum(g_ex, g_fin), np.where(hard, g_fin, g_ex))
    # a decision that went the other way, in the band or outside it: the
    # token is the greedy token of the head the reference's own decision
    # does not pick, and not that of the head it picks
    lc = np.log(np.maximum(conf, 1e-38)) - lthr
    fin_arg = np.asarray(jnp.argmax(fin, -1))[:n]
    own = np.where(lc <= 0, fin_arg, arg)
    other = np.where(lc <= 0, arg, fin_arg)
    flip = (pick == other) & (pick != own)
    flip[0] = False
    w = int(gaps.argmax())
    return Judged(max_gap=float(gaps.max()), n_tokens=n,
                  n_ambiguous=int(amb.sum()), n_hard=int(hard[1:].sum()),
                  worst_at=w, worst_lc=float(lc[w]), n_flips=int(flip.sum()),
                  flip_lc=float(np.abs(lc[flip]).max()) if flip.any()
                  else 0.0)


def judge_control(ref: Reference, ctrl: Reference, prompt,
                  served: List[int], c_thr: float, band: float) -> Judged:
    """The control in the program's place: at each decision position of the
    same prompt and served tokens, the lower-precision reference's own
    greedy token (from the head its own confidence picks), judged against
    the float32 reference as a served token would be."""
    served = np.asarray([int(x) for x in served], np.int32)
    n = len(served)
    lthr = np.log(c_thr)
    ex, fin, conf, arg, hard = ctrl.logits(
        np.asarray(prompt), served,
        lambda conf, arg: np.log(np.maximum(conf, 1e-38)) - lthr <= 0)
    pick = np.where(hard, np.asarray(jnp.argmax(fin, -1))[:n], arg)
    return judge(ref, prompt, served, c_thr, band, pick=pick)
