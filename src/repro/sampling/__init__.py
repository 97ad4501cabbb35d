from repro.sampling.warp import (
    draw_keyed,
    sample_categorical,
    stream_key,
    warp_logits,
    warp_probs,
)

__all__ = ["warp_logits", "warp_probs", "sample_categorical", "stream_key", "draw_keyed"]
