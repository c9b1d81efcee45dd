"""Model zoo: three block families behind one seam.

`gpt.py` is the reference's one model, a GPT-style decoder LM re-exported at
reference `models/__init__.py:1`, mirrored here by the pure-JAX twin; it
trains and serves. `latent.py` is the latent-attention family (latent
attention with a learned key selection, window layers with their own latent,
a sigmoid-routed expert layer of which one chip holds a share; in its second
published form grouped differential heads on the latent, no selection, a
multi-stream residual and PolyNorm experts); it is served only. `hybrid.py` is
the hybrid state-space family (Mamba-1 selective-state layers beside a few
attention layers of one K/V head; a recurrent state a slot beside the pages);
it is served only. `family(cfg)`
gives the module that implements a config: the serve programs, the engine and the samplers call the model through it (`init_params`,
`forward`, `forward_cached`, `init_kv_cache`, `init_paged_cache`, `page_kinds`,
`select_lanes`, `merge_lanes`, `counters`, `max_context`, `cached_decode_exact`)."""

from tpukit.model import gpt, hybrid, latent
from tpukit.model.gpt import (  # noqa: F401
    GPTConfig,
    TransformerDecoderLM,
    apply_decoder_layers,
    apply_embeddings,
    apply_head,
    forward,
    init_params,
)
from tpukit.model.hybrid import HybridConfig  # noqa: F401
from tpukit.model.latent import LatentConfig, ServedOnlyError  # noqa: F401


def family(cfg):
    """The module that implements `cfg`'s block family."""
    if isinstance(cfg, GPTConfig):
        return gpt
    if isinstance(cfg, LatentConfig):
        return latent
    if isinstance(cfg, HybridConfig):
        return hybrid
    raise TypeError(f"no block family for a config of type {type(cfg).__name__}")
