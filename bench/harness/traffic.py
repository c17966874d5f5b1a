"""The one request generator every traffic mix is read by.

A traffic file (``bench/traffic/<name>.json``) holds parameters only:

- ``policies``: policy columns in every request, ``{"name", "params"}``;
- ``draw``: optional fresh candidates per request, each the policy
  ``name`` with its ``param`` drawn uniformly from ``[low, high]`` (rounded
  to ``decimals``), ``per_request`` of them, never repeating within a run;
- ``fresh_seeds``: that many new seeds in every request, derived from the
  run's seed and never repeating within a run; or
- ``paired_seeds``: the same seeds in every request, as a tuning loop
  compares its candidates on fixed paired traces;
- ``check_per_request``: the plain reference checks one cell from each
  of that many equal slices of every request's batch; or
- ``check_cells``: it checks that many cells drawn from the window;
- ``trace_requests``: how many requests a traced run profiles.

Every fresh seed and every draw derives from the run's ``--seed`` and the
cell name, so one seed gives one request stream.  Requests are sent by
one client in a closed loop: the next one when the last has returned.
"""
from __future__ import annotations

import hashlib
import random
from dataclasses import dataclass
from typing import List, Tuple


def derive(*parts) -> int:
    """A 32-bit seed from the run's seed, the cell and a purpose."""
    digest = hashlib.sha256("/".join(map(str, parts)).encode()).digest()
    return int.from_bytes(digest[:4], "big")


@dataclass(frozen=True)
class Request:
    """One what-if query: policy columns x seeds."""

    index: int
    policies: Tuple[dict, ...]
    seeds: Tuple[int, ...]

    @property
    def cells(self) -> int:
        return len(self.policies) * len(self.seeds)


class Stream:
    """The request stream of one (cell, seed).  ``warmup()`` is one request
    of the window's size on seeds and draws the window never uses."""

    def __init__(self, traffic: dict, workload: str, seed: int):
        self.traffic = traffic
        self.workload = workload
        self.seed = seed
        self._seen_seeds = set()
        self._seen_draws = set()
        self._draw_rng = random.Random(derive(workload, seed, "draw"))

    def _seeds(self, tag) -> Tuple[int, ...]:
        if "paired_seeds" in self.traffic:
            return tuple(int(s) for s in self.traffic["paired_seeds"])
        return self._new_seeds(tag, int(self.traffic["fresh_seeds"]))

    def _new_seeds(self, tag, n) -> Tuple[int, ...]:
        out = []
        k = 0
        while len(out) < n:
            s = derive(self.workload, self.seed, "seed", tag, k)
            k += 1
            if s not in self._seen_seeds:
                self._seen_seeds.add(s)
                out.append(s)
        return tuple(out)

    def _draws(self) -> List[dict]:
        spec = self.traffic.get("draw")
        if not spec:
            return []
        out = []
        while len(out) < int(spec["per_request"]):
            value = round(self._draw_rng.uniform(spec["low"], spec["high"]),
                          int(spec["decimals"]))
            if value in self._seen_draws:
                continue
            self._seen_draws.add(value)
            out.append({"name": spec["name"],
                        "params": {spec["param"]: value}})
        return out

    def _request(self, index, tag) -> Request:
        policies = tuple(self.traffic["policies"]) + tuple(self._draws())
        return Request(index=index, policies=policies,
                       seeds=self._seeds(tag))

    def warmup(self) -> Request:
        return self._request(-1, "warmup")

    def request(self, index: int) -> Request:
        return self._request(index, index)
