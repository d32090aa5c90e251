"""The benchmark's token generator: one general generator that every traffic
file parameterises.

A copy of the arithmetic of the program's ``data/synthetic.py:
TokenPipeline``, kept here so that a change to the program cannot change
the benchmark's inputs: each agent draws from its own Zipf distribution
(exponent ``zipf_base + zipf_agent_spread * a / (A - 1)``, ranks shifted
cyclically by ``a * V // A``), so the agents' objectives differ (non-IID),
and every step's rows come from ``SeedSequence([seed, step])``.
"""
from __future__ import annotations

from typing import Dict, Iterator

import numpy as np


class ZipfAgents:
    """Batches ``{"tokens", "labels"}`` of shape (agents, batch, seq_len),
    int32; the labels are the tokens shifted by one."""

    def __init__(self, traffic: dict, vocab: int, n_agents: int, seed: int):
        self.vocab = vocab
        self.n_agents = n_agents
        self.seq_len = int(traffic["seq_len"])
        self.batch = int(traffic["batch_per_agent"])
        self.seed = seed
        self.step = 0
        spread = max(n_agents - 1, 1)
        ranks = np.arange(1, vocab + 1, dtype=np.float64)
        self.cdfs = []
        for a in range(n_agents):
            ex = traffic["zipf_base"] + traffic["zipf_agent_spread"] * a / spread
            p = ranks ** (-ex)
            p /= p.sum()
            cdf = np.roll(p, (a * vocab) // max(n_agents, 1)).cumsum()
            self.cdfs.append(cdf / cdf[-1])

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        return self

    def __next__(self) -> Dict[str, np.ndarray]:
        rng = np.random.default_rng(
            np.random.SeedSequence([self.seed, self.step]))
        self.step += 1
        toks = np.empty((self.n_agents, self.batch, self.seq_len + 1),
                        np.int32)
        for a in range(self.n_agents):
            # what Generator.choice(vocab, p=...) draws, with the
            # cumulative distribution kept from one step to the next
            u = rng.random((self.batch, self.seq_len + 1))
            toks[a] = self.cdfs[a].searchsorted(u, side="right")
        return {"tokens": toks[..., :-1], "labels": toks[..., 1:]}


GENERATORS = {"zipf_agents": ZipfAgents}


def make_stream(traffic: dict, vocab: int, n_agents: int, seed: int):
    return GENERATORS[traffic["generator"]](traffic, vocab, n_agents, seed)
