"""A cell at a size that the CPU can run: granite-3-2b's smoke preset
widened to 4 layers at d_model 512 and a 4096-token vocabulary, so that
near-ties, which a lower precision flips, occur; with its draft, under a
tiny closed loop.  The tests drive ``run.run_cell`` with it, past the
harness's look for a chip.  With ``chips`` above 1 the cell asks for that
many chips and serves one client a row on the sharded engine."""
from __future__ import annotations

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))


def family(path: Path):
    """A family module, loaded as the harness loads one."""
    from run import load_module

    return load_module(path)


def spec(attention_impl: str = "xla", chips: int = 1) -> dict:
    dims = {"hidden_size": 512, "intermediate_size": 1024, "num_hidden_layers": 4,
            "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 64,
            "vocab_size": 4096, "tie_word_embeddings": True, "rope_theta": 10000.0,
            "rms_norm_eps": 1e-6}
    draft = dict(dims, hidden_size=256, intermediate_size=512, num_hidden_layers=1,
                 num_attention_heads=4, num_key_value_heads=1)
    rows = 4 if chips > 1 else 3
    return {
        "cell": {"name": "smoke", "chips": chips},
        "family": family(BENCH / "families" / "dense.py"),
        "config": dict(dims, family="dense", arch="granite-3-2b", smoke=True,
                       reference="dense_reference",
                       weight_seed=20260101,
                       program_replace={"vocab": 4096, "n_layers": 4, "d_model": 512, "n_heads": 8,
                                        "n_kv_heads": 2, "d_ff": 1024}, draft=draft),
        "traffic": {"clients": rows, "requests_per_client": 4,
                    "prompt": {"dist": "loguniform", "min": 8, "max": 40},
                    "output": {"dist": "uniform", "min": 8, "max": 24},
                    "first_output": {"dist": "uniform", "min": 4, "max": 24},
                    "prewindow_steps": 2, "check_len": 64,
                    "engine": {"streams": rows, "max_cache": 128, "block_size": 16,
                               "attention_impl": attention_impl, "pipeline": True,
                               "ragged": True, "verifier": "specinfer", "K": 2, "L1": 2,
                               "L2": 2, "temperature": 1.0, "top_p": 1.0}},
        "per_layer": [{"name": n, "unit": "%"} for n in (
            "occupancy", "pad_frac", "begin_ms", "verify_ms", "block_eff", "idle_frac",
            "step_mfu")],
        "end_to_end": [{"name": n, "unit": "u"} for n in (
            "out_tok_s", "itl_p95_ms", "ttft_p50_ms", "setup_s")],
    }
