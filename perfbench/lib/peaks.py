"""The yardstick: published peaks per chip, and the least time a model's
work requires of them.  The counts of parameters, bytes and FLOPs are the
configuration's family's (families/).  Copied from
horovod_tpu/perf/costmodel.py (PEAKS and the 6*N convention) so that a later
PR can change the program and not this.
"""

from __future__ import annotations

from . import spec

# Keyed by jax's ``device_kind``.  A kind that is not here is an error.
PEAKS = {
    "TPU v4": {"bf16_tflops": 275.0, "hbm_gbps": 1200.0, "hbm_gb": 32.0,
               "source": "Google Cloud documentation, 'TPU v4'"},
    "TPU v5 lite": {"bf16_tflops": 197.0, "hbm_gbps": 819.0, "hbm_gb": 16.0,
                    "source": "Google Cloud documentation, 'TPU v5e'"},
    "TPU v5p": {"bf16_tflops": 459.0, "hbm_gbps": 2765.0, "hbm_gb": 95.0,
                "source": "Google Cloud documentation, 'TPU v5p'"},
    "TPU v6 lite": {"bf16_tflops": 918.0, "hbm_gbps": 1640.0, "hbm_gb": 32.0,
                    "source": "Google Cloud documentation, 'TPU v6e'"},
}


def device_peaks(device_kind):
    if device_kind not in PEAKS:
        raise ValueError(f"device_kind {device_kind!r} is not in perfbench's "
                         f"peaks table ({', '.join(PEAKS)})")
    return PEAKS[device_kind]


def train_flops_per_token(config, seq):
    """Required training FLOPs a token, as the configuration's family
    reckons them (recomputation not counted)."""
    return spec.family(config).train_flops_per_token(config, seq)


def serve_required_seconds(config, peaks, valid_tokens, context_tokens,
                           ticks, itemsize=2):
    """Least seconds the chip needs for what ``ticks`` engine ticks were
    asked to do: ``valid_tokens`` new tokens through every matmul they pass
    through, attending to ``context_tokens`` cached positions (summed over
    ticks), a tick reading the weights its share of the tokens needs and the
    cache of the positions attended to.  Returns (seconds, which bound
    binds)."""
    fam = spec.family(config)
    flops = (2.0 * fam.param_counts(config)["matmul"] * valid_tokens
             + fam.attn_flops_per_position(config) * context_tokens)
    bytes_ = (fam.tick_weight_bytes(config, valid_tokens / ticks, itemsize)
              * ticks
              + fam.cache_bytes_per_position(config, itemsize)
              * context_tokens)
    t_flops = flops / (peaks["bf16_tflops"] * 1e12)
    t_bytes = bytes_ / (peaks["hbm_gbps"] * 1e9)
    return max(t_flops, t_bytes), ("flops" if t_flops >= t_bytes else "bytes")
