"""Model zoo: the CTR model families the reference benchmarks, rebuilt TPU-first.

Reference coverage (`documents/en/benchmark.md:6-16`, `examples/`,
`test/benchmark/criteo_deepctr.py`): WDL (Wide&Deep), DeepFM, xDeepFM at dims 9/64,
the LR subclass example (`examples/criteo_lr_subclass.py`), plus DLRM (the reference's
PMem paper workload) and a two-tower retrieval model. Seven language-model towers
behind a token table ride the same train path: NemotronH (`nemotron_h.py`: Mamba-2,
attention, routed experts), JoyAI-LLM-Flash (`joyai_flash.py`: latent attention,
routed SwiGLU experts, a multi-token-prediction module), Solar-Open2
(`solar_open2.py`: gated delta-rule linear attention, gated softmax attention
without positions, routed SwiGLU experts; a share of the heads held) and ZAYA1
(`zaya1.py`: compressed convolutional attention, a router MLP whose state
travels up the stack, top-1 experts, the token table tied to the head and
trained densely) and Ouro (`ouro.py`: a dense stack walked several times over
shared weights as one scanned body, an exit gate a pass, the expected loss over
the exits through one head, computed inside the walk) and Granite 4.0-H
(`granite_hybrid.py`: nine Mamba-2 layers in ten, a SwiGLU after every mixer,
four muP scalars, a tied head; documents packed into a sequence, the state, the
convolution and the attention mask reset at every document start) and SDAR
(`sdar_moe.py`: the Qwen3-MoE decoder trained by block diffusion over a noised
and a clean copy of every sequence, the block mask in the fused attention
kernel, per-head q / k norms, softmax top-k routing).

TPU-first layout decision (differs deliberately from the reference's per-feature
DeepCTR `Embedding` layers): all categorical fields share ONE row-sharded table, with
per-field id offsets applied by the data pipeline (`data/criteo.py`). A batch pulls
(B, F) ids in a single all_to_all exchange instead of F small ones — F=26 tiny
collectives would be ICI-latency-bound. The first-order (wide/linear) weight rides the
same table as column 0 (tables store dim+1 columns), so WDL/DeepFM need no second
exchange for their linear term.
"""

from .ctr import (MLP, LogisticRegression, WideDeep, DeepFM, XDeepFM, DCN,
                  DLRM, make_lr, make_wdl, make_deepfm, make_xdeepfm,
                  make_dcn, make_dlrm, CRITEO_NUM_SPARSE, CRITEO_NUM_DENSE)
from .two_tower import TwoTower, make_two_tower, in_batch_softmax_loss
from .sequential import (SASRec, bert4rec_mask_id, make_bert4rec,
                         make_sasrec, sasrec_bce_loss,
                         synthetic_masked_sequences, synthetic_sequences)
from .nemotron_h import NemotronH, make_nemotron_h, softmax_xent
from .joyai_flash import JoyAIFlash, make_joyai_flash, mtp_xent
from .solar_open2 import SolarOpen2, kda_chunked, make_solar_open2
from .zaya1 import Zaya1, make_zaya1
from .ouro import Ouro, expected_exit_loss, make_ouro
from .granite_hybrid import GraniteHybrid, make_granite_hybrid
from .sdar_moe import SdarMoE, corrupt_blocks, diffusion_xent, make_sdar_moe

_FAMILIES = {
    "lr": make_lr, "wdl": make_wdl, "deepfm": make_deepfm,
    "xdeepfm": make_xdeepfm, "dcn": make_dcn, "dlrm": make_dlrm,
    "two_tower": make_two_tower,
    "sasrec": make_sasrec,
    "bert4rec": make_bert4rec,
    "nemotron_h": make_nemotron_h,
    "joyai_flash": make_joyai_flash,
    "solar_open2": make_solar_open2,
    "zaya1": make_zaya1,
    "ouro": make_ouro,
    "granite_hybrid": make_granite_hybrid,
    "sdar_moe": make_sdar_moe,
}


def from_config(config: dict, **overrides):
    """Rebuild a zoo model from its `EmbeddingModel.config` recipe (written into
    standalone serving exports by `export.py`; the reference ships the whole graph in
    a SavedModel instead, `tensorflow/exb.py:506-547`). The recipe stores exactly its
    factory's keyword arguments, so dispatch is uniform."""
    import jax.numpy as jnp

    cfg = dict(config)
    cfg.pop("serving_overrides", None)  # applied by callers (export.py) as overrides
    cfg.update(overrides)
    family = cfg.pop("family")
    if family not in _FAMILIES:
        raise ValueError(f"unknown model family {family!r}")
    cfg["compute_dtype"] = jnp.dtype(cfg.get("compute_dtype", "bfloat16"))
    for k in ("hidden", "cin_layers", "bottom", "top", "tower"):
        if k in cfg:
            cfg[k] = tuple(cfg[k])
    return _FAMILIES[family](**cfg)


__all__ = [
    "MLP", "LogisticRegression", "WideDeep", "DeepFM", "XDeepFM", "DCN",
    "DLRM", "make_lr", "make_wdl", "make_deepfm", "make_xdeepfm", "make_dcn",
    "make_dlrm",
    "from_config",
    "TwoTower", "make_two_tower", "in_batch_softmax_loss",
    "SASRec", "make_sasrec", "sasrec_bce_loss", "synthetic_sequences",
    "make_bert4rec", "bert4rec_mask_id", "synthetic_masked_sequences",
    "NemotronH", "make_nemotron_h", "softmax_xent",
    "JoyAIFlash", "make_joyai_flash", "mtp_xent",
    "SolarOpen2", "make_solar_open2", "kda_chunked",
    "Zaya1", "make_zaya1",
    "Ouro", "make_ouro", "expected_exit_loss",
    "GraniteHybrid", "make_granite_hybrid",
    "SdarMoE", "make_sdar_moe", "diffusion_xent", "corrupt_blocks",
    "CRITEO_NUM_SPARSE", "CRITEO_NUM_DENSE",
]
