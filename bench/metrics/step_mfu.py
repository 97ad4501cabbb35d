"""Device: the whole step's share of the chip's bf16 peak.  Operations of
the target and draft forwards over the real tokens the window processed
(tree nodes, draft ingest and drafting tokens, prefill tokens; no padding),
counted from shapes by the configuration's family
(``bench/families/<family>.py``), over window x chips x peak."""


def read(rec):
    ops = rec["model_ops"]
    if not ops or rec["window_ns"] <= 0:
        return None
    return 100.0 * ops / (rec["window_ns"] / 1e9 * rec["chips"] * rec["peak"]["flops_bf16"])
