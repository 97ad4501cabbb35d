"""Shared benchmark infrastructure.

Synthetic (p, q) processes stand in for the paper's model/dataset grid: the
verification algorithms consume only per-node next-token distributions, so a
table-driven process exercises exactly the same code while staying CPU-cheap.

  * families  — target:draft size-ratio analogues (the paper's Qwen ~64:1,
    Gemma ~100:1, Llama ~9:1) realised as base divergence levels + a
    depth-growth coefficient (the Fig. 1 mechanism).
  * domains   — dataset analogues (seeds; math/code/writing/translation
    differ only through the induced (p, q) statistics here).
  * sampling  — the paper's 8 configurations: temperatures at top_p = 1 and
    nucleus settings at temperature 1.

The latency model (Eq. 11) is calibrated from the TPU roofline of the paper's
own Llama-3 70B/8B pair (197 TFLOP/s bf16, 819 GB/s HBM per chip) — see
``analytic_latency``.
"""
from __future__ import annotations

import zlib

import numpy as np

from repro.core.delayed import LatencyModel

PEAK_FLOPS = 197e12
HBM_BW = 819e9
LINK_BW = 50e9


class SyntheticProcess:
    """Deterministic per-context (p, q) tables with controllable divergence
    growth in depth and sampling-parameter warping."""

    def __init__(self, vocab: int, seed: int, base_div: float, depth_div: float,
                 temperature: float = 1.0, top_p: float = 1.0, concentration: float = 0.6):
        self.vocab = vocab
        self.seed = seed
        self.base_div = base_div
        self.depth_div = depth_div
        self.temperature = temperature
        self.top_p = top_p
        self.concentration = concentration
        self._cache: dict = {}

    def _warp(self, d):
        if self.temperature != 1.0:
            d = np.power(np.clip(d, 1e-12, None), 1.0 / self.temperature)
            d = d / d.sum()
        if self.top_p < 1.0:
            order = np.argsort(d)[::-1]
            cs = np.cumsum(d[order])
            keep_n = int(np.searchsorted(cs, self.top_p) + 1)
            mask = np.zeros_like(d, dtype=bool)
            mask[order[:keep_n]] = True
            d = np.where(mask, d, 0.0)
            d = d / d.sum()
        return d

    def _dists(self, ctx):
        if ctx not in self._cache:
            rng = np.random.default_rng(zlib.crc32(repr(("sp", self.seed, ctx)).encode()))
            # per-region modulation: different trajectory regions have
            # different draft alignment AND different peakedness (easy
            # low-entropy spans accept deep blocks; hard flat spans don't) —
            # the context-dependence the NDE selector exploits (Sec. 6).
            # Both are functions of the region key, so root-level entropy/KL
            # features are predictive of downstream acceptance.
            region = np.random.default_rng(zlib.crc32(repr(("mod", self.seed, ctx[:1])).encode()))
            mod = region.uniform(-0.25, 0.35)
            conc = self.concentration * region.uniform(0.25, 3.0)
            p = rng.dirichlet(np.full(self.vocab, conc))
            noise = rng.dirichlet(np.full(self.vocab, conc))
            w = float(np.clip(self.base_div + mod + self.depth_div * len(ctx), 0.02, 0.97))
            q = (1 - w) * p + w * noise
            # the paper warps the TARGET sampling distribution; the draft
            # proposes from its own (warped) head as engines do
            self._cache[ctx] = (self._warp(p), self._warp(q))
        return self._cache[ctx]

    def p(self, ctx):
        return self._dists(tuple(ctx))[0]

    def q(self, ctx):
        return self._dists(tuple(ctx))[1]


# paper-analogue grid
FAMILIES = {
    # name: (base divergence, depth growth)  ~ target:draft ratio analogue
    "qwen-64to1": (0.35, 0.10),
    "gemma-100to1": (0.55, 0.15),
    "llama-9to1": (0.15, 0.06),
}
DOMAINS = [0, 1, 2, 3, 4]  # math-e, math-h, code, writing, translation analogues
SAMPLING = [
    (0.2, 1.0), (0.4, 1.0), (0.6, 1.0), (0.8, 1.0), (1.0, 1.0), (1.2, 1.0),
    (1.0, 0.9), (1.0, 0.99),
]
SAMPLING_QUICK = [(0.2, 1.0), (0.6, 1.0), (1.0, 1.0), (1.0, 0.9)]


def make_process(family: str, domain: int, temperature: float, top_p: float,
                 vocab: int = 8) -> SyntheticProcess:
    b, g = FAMILIES[family]
    return SyntheticProcess(vocab, seed=1000 * DOMAINS.index(domain) + zlib.crc32(family.encode()) % 997,
                            base_div=b, depth_div=g, temperature=temperature, top_p=top_p)


def analytic_latency(n_params_target: float, n_params_draft: float,
                     kv_bytes_per_tok_t: float, kv_bytes_per_tok_d: float,
                     chips: int = 8, overhead: float = 20e-6,
                     tree_tok_frac: float = 0.02) -> LatencyModel:
    """Decode-step latency from the roofline (memory-bound regime):
    t(l) = overhead + (2*N + l*kv)/HBM_BW/chips.  Matches Eq. 11's affine
    form; the paper instead microbenchmarks — see DESIGN.md.  tree_tok_frac
    is the measured marginal target-pass cost per speculation token
    (benchmarks/tree_economics.py)."""
    t_p_base = overhead + 2 * n_params_target / (HBM_BW * chips)
    return LatencyModel(
        t_q_base=overhead + 2 * n_params_draft / (HBM_BW * chips),
        t_q_per_tok=kv_bytes_per_tok_d / (HBM_BW * chips),
        t_p_base=t_p_base,
        t_p_per_tok=kv_bytes_per_tok_t / (HBM_BW * chips),
        t_p_per_tree_tok=tree_tok_frac * t_p_base,
    )


def paper_pair_latency(chips: int = 8) -> LatencyModel:
    """Llama-3 70B / 8B decode latency on `chips` v5e chips."""
    from repro.configs.paper_llama70b_8b import DRAFT, TARGET

    kv_t = TARGET.n_layers * 2 * TARGET.n_kv_heads * TARGET.hd * 2
    kv_d = DRAFT.n_layers * 2 * DRAFT.n_kv_heads * DRAFT.hd * 2
    return analytic_latency(TARGET.param_count(), DRAFT.param_count(), kv_t, kv_d, chips)


FAMILY_LATENCY = {
    # scale draft size by the family ratio analogue
    "qwen-64to1": (32e9, 0.5e9),
    "gemma-100to1": (27e9, 0.27e9),
    "llama-9to1": (70e9, 8e9),
}


def family_latency(family: str, chips: int = 8) -> LatencyModel:
    nt, nd = FAMILY_LATENCY[family]
    return analytic_latency(nt, nd, nt / 4e6, nd / 4e6, chips)


# ------------------------------------------------------- bench JSON schema ---

BENCH_SCHEMA = 1


def write_bench_json(path: str, name: str, config: dict, results: list[dict]) -> dict:
    """Emit a bench run as the stable machine-readable ``BENCH_<name>.json``
    document the regression gate (scripts/bench_smoke.sh) and the checked-in
    baselines (benchmarks/baselines/) consume:

        {"bench": <name>, "schema": BENCH_SCHEMA,
         "config": {...flags of the run...},
         "results": [ {...one row per measured point...} ]}

    ``config`` holds the knobs that define the run (arch, verifier, action,
    sizes); each ``results`` row holds the measured numbers for one point
    (tokens/sec per mode, commit calls, blocks peak, exactness booleans).  The
    writer is schema-versioned so gates can refuse documents they do not
    understand instead of misreading them.
    """
    import json

    doc = {"bench": name, "schema": BENCH_SCHEMA, "config": config, "results": results}
    with open(path, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    return doc
