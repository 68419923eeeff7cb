"""The one traffic generator: a workload file's parameters -> requests.

Every seed gets the SAME lengths and inter-arrival gaps, drawn once from
the workload file's ``draw_seed``: a run's amount and shape of work is
fixed by the cell. ``--seed`` picks the words of every prompt (so no two
prompts share a prefix beyond the chat template's first tokens), the
weights and, in an open loop, the PHASE: the cycle of (gap, prompt,
output) triples is begun at a seeded point, so every seed offers the same
requests with the same neighbours, in another order. At the 42 requests
a window holds at this system's capacity the 95th percentile is the
third-largest value and belongs to whichever arrivals fall behind a long
engine step; runs of several seeds sample several such alignments, and
the driver's median over them does not hang on one (PERF.md, PR 26: the
same sequence for every seed repeated within 1% and hid exactly that). A
closed loop has no schedule to shift: its callers take the pool in its
fixed order, and the system's own pace sets the interleaving.

Open loop (``arrivals``): ``floor(rate * seconds)`` requests, Gamma gaps
with the given coefficient of variation rescaled to fill the window
exactly, each request DUE at a fixed offset whatever the system does.
Closed loop (``clients``): a pool of requests that clients take in pool
order, each sending its next when its last completed; the pool is a short
``cycle`` of lengths repeated, so that runs which get through different
numbers of requests still do nearly the same work.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np


@dataclasses.dataclass(frozen=True)
class Planned:
    """One request as planned: when it is due (seconds from the window's
    start; ``None`` in a closed loop), its prompt length in tokens AS THE
    SERVER COUNTS THEM (chat template included) and its output budget."""

    index: int
    due_s: float | None
    prompt_tokens: int
    output_tokens: int


def draw_lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` token counts from ``spec``: ``lognormal`` (median, sigma) or
    ``loguniform``, clipped to ``[min, max]``."""
    lo, hi = int(spec["min"]), int(spec["max"])
    if lo < 1 or hi < lo:
        raise ValueError(f"bad length range [{lo}, {hi}]")
    dist = spec["dist"]
    if dist == "lognormal":
        x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    elif dist == "loguniform":
        x = np.exp(rng.uniform(math.log(lo), math.log(hi + 1), n))
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


def draw_gaps(rate_per_s: float, cv: float, n: int, seconds: float,
              rng: np.random.Generator) -> np.ndarray:
    """``n`` inter-arrival gaps, Gamma(1/cv^2) (cv 1 = Poisson), rescaled
    so that they sum to ``seconds``: the offered rate is then exactly
    ``n / seconds`` in every run."""
    if n < 1:
        raise ValueError("the window holds no arrival: raise the rate or "
                         "the seconds")
    shape = 1.0 / (cv * cv)
    gaps = rng.gamma(shape, 1.0 / (rate_per_s * shape), n)
    return gaps * (seconds / gaps.sum())


def plan(workload: dict, seconds: float, seed: int) -> list[Planned]:
    """The requests of one run: lengths and gaps from the workload's
    ``draw_seed``; in an open loop ``seed`` picks where the cycle begins."""
    fixed = np.random.default_rng(int(workload["draw_seed"]))
    if "arrivals" in workload:
        arr = workload["arrivals"]
        n = int(math.floor(arr["rate_per_s"] * seconds))
        gaps = draw_gaps(arr["rate_per_s"], arr["cv"], n, seconds, fixed)
    else:
        n = int(workload["cycle"])
        gaps = None
    prompts = draw_lengths(workload["prompt_tokens"], n, fixed)
    outputs = draw_lengths(workload["output_tokens"], n, fixed)
    cap = int(workload["max_total_tokens"])
    outputs = np.minimum(outputs, cap - prompts)
    if (outputs < 1).any():
        raise ValueError("prompt range leaves no room for output under "
                         f"max_total_tokens={cap}")
    order = np.arange(n)
    due = None
    if gaps is not None:
        first = int(np.random.default_rng([int(seed), 5]).integers(n))
        order = np.roll(order, -first)
        gaps = gaps[order]
        due = np.cumsum(gaps) - gaps          # first at 0, last < seconds
    else:
        # closed loop: the cycle of lengths repeats until the pool is
        # full (each entry still gets a text of its own)
        order = np.resize(order, int(workload["pool"]))
    return [Planned(i, None if due is None else float(due[i]),
                    int(prompts[k]), int(outputs[k]))
            for i, k in enumerate(order)]


class PromptWriter:
    """Text that the server's tokenizer counts to EXACTLY the planned
    length. Words are seeded random letter strings; the byte-level
    pre-tokenizer splits on spaces, so a prompt's token count is the sum
    of its words' counts plus the chat template's — checked, not assumed,
    on every prompt."""

    def __init__(self, tokenizer, render, seed: int, n_words: int = 4096):
        self.tok = tokenizer
        self.render = render            # content -> the server's prompt
        rng = np.random.default_rng([int(seed), 7])
        letters = np.array(list("abcdefghijklmnopqrstuvwxyz"))
        words, seen = [], set()
        while len(words) < n_words:
            w = "".join(rng.choice(letters, int(rng.integers(3, 10))))
            if w not in seen:
                seen.add(w)
                words.append(w)
        self.words = words
        self.counts = np.array(
            [len(tokenizer.encode(" " + w)) for w in words])
        ones = [w for w, c in zip(words, self.counts) if c == 1]
        self.unit = ones[0] if ones else self._find_unit()
        # template + a first word that carries no leading space
        self.base = len(tokenizer.encode(render(self.unit)))
        self.rng = rng

    def _find_unit(self) -> str:
        for piece, _ in sorted(self.tok.vocab.items(), key=lambda kv: kv[1]):
            if (piece.startswith("Ġ") and piece[1:].isalpha()
                    and piece[1:].isascii()
                    and len(self.tok.encode(piece[1:])) == 1
                    and len(self.tok.encode(" " + piece[1:])) == 1):
                return piece[1:]
        raise RuntimeError("the tokenizer has no one-token word")

    def write(self, n_tokens: int) -> str:
        need = n_tokens - self.base
        if need < 0:
            raise ValueError(f"a prompt of {n_tokens} tokens is shorter "
                             f"than the chat template ({self.base})")
        picked, total = [], 0
        while total < need:
            i = int(self.rng.integers(len(self.words)))
            c = int(self.counts[i])
            if total + c > need:
                break
            picked.append(self.words[i])
            total += c
        picked += [self.unit] * (need - total)
        text = " ".join([self.unit] + picked)
        got = len(self.tok.encode(self.render(text)))
        if got != n_tokens:
            raise RuntimeError(f"prompt came to {got} tokens, planned "
                               f"{n_tokens}")
        return text
