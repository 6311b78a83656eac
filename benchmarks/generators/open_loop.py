"""Open loop: requests are due on a schedule whether or not earlier ones
have finished (independent users).  The rate is fixed in the mix's file.

The schedule has two parts with their own populations: the ramp
(``ramp_s`` before the window, part of set-up) and the window.  The
window holds exactly round(rate x seconds) requests; sizes, gaps and
their order come from the mix's ``population_seed``, so every seed meets
the same schedule and fills it with its own prompt tokens."""
from __future__ import annotations

import numpy as np

from .. import serve
from ..traffic_gen import (Request, make_prompt, poisson_offsets,
                           population)


class Source:
    def __init__(self, mix, seed, sizes, seconds, rehearse):
        if mix["arrivals"]["process"] != "poisson":
            raise SystemExit("benchmark: open_loop knows poisson arrivals")
        rate = float(mix["arrivals"]["rate_per_s"])
        scale = sizes["T"] / 2048.0 if rehearse else 1.0
        rng = np.random.default_rng(seed)               # prompt tokens
        plan = np.random.default_rng([int(mix["population_seed"]), 1])
        self.rel = []                          # (offset from start, sizes)
        for t_from, span in ((0.0, float(mix["ramp_s"])),
                             (float(mix["ramp_s"]), seconds)):
            offs = poisson_offsets(rate, span, plan)
            pop = population(mix, len(offs), scale)
            order = plan.permutation(len(pop))
            self.rel += [(t_from + o, pop[i]) for o, i in zip(offs, order)]
        self.rng, self.vocab = rng, sizes["V_published"]
        self.t_start, self.next_i, self.stopped = None, 0, False

    def prompt_lens(self):
        return [p for _, (p, _) in self.rel]

    def start(self, t):
        self.t_start = t

    def due(self, now):
        out = []
        while (not self.stopped and self.next_i < len(self.rel)
               and self.t_start + self.rel[self.next_i][0] <= now):
            off, (p, o) = self.rel[self.next_i]
            out.append(Request(make_prompt(self.rng, p, self.vocab), o,
                               self.t_start + off))
            self.next_i += 1
        return out

    def next_due(self):
        if self.stopped or self.next_i >= len(self.rel):
            return None
        return self.t_start + self.rel[self.next_i][0]

    def completed(self, rid, now):
        pass

    def stop(self):
        self.stopped = True


def run(ctx):
    serve.run(ctx, Source)
