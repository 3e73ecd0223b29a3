"""The yardstick: published peaks per chip, and the operations and bytes a
model's work requires.  Copied from horovod_tpu/perf/costmodel.py (PEAKS and
the 6*N convention) so that a later PR can change the program and not this.
"""

from __future__ import annotations

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


def param_counts(config):
    """{'matmul': parameters that every token is multiplied by, 'embed': the
    embedding table (a gather, no FLOPs), 'total'} of a dense GQA decoder
    with a gated FFN, no biases and an untied head."""
    d, L = config["hidden_size"], config["num_hidden_layers"]
    hd = d // config["num_attention_heads"]
    kv = config["num_key_value_heads"] * hd
    per_layer = d * d + 2 * d * kv + d * d + 3 * d * config["intermediate_size"]
    head = d * config["vocab_size"]
    norms = (2 * L + 1) * d
    return {"matmul": L * per_layer + head, "embed": head,
            "total": L * per_layer + 2 * head + norms}


def kv_bytes_per_token(config, itemsize=2):
    hd = config["hidden_size"] // config["num_attention_heads"]
    return (2 * config["num_hidden_layers"] * config["num_key_value_heads"]
            * hd * itemsize)


def train_flops_per_token(config, seq):
    """Required training FLOPs a token: 6 per matmul parameter (2 forward, 4
    backward; recomputation not counted) plus causal attention's score and
    value products, 6*seq*hidden per layer (2*seq*hidden forward at the
    causal half, tripled)."""
    n = param_counts(config)["matmul"]
    attn = 6.0 * seq * config["hidden_size"] * config["num_hidden_layers"]
    return 6.0 * n + attn


def serve_required_seconds(config, peaks, valid_tokens, context_tokens,
                           ticks, itemsize=2):
    """Least seconds the chip needs for what ``ticks`` engine ticks were
    asked to do: ``valid_tokens`` new tokens through every matmul,
    attending to ``context_tokens`` cached positions (summed over ticks).
    Returns (seconds, which bound binds)."""
    n = param_counts(config)["matmul"]
    flops = (2.0 * n * valid_tokens
             + 4.0 * config["hidden_size"] * config["num_hidden_layers"]
             * context_tokens)
    bytes_ = (itemsize * n * ticks
              + kv_bytes_per_token(config, itemsize) * context_tokens)
    t_flops = flops / (peaks["bf16_tflops"] * 1e12)
    t_bytes = bytes_ / (peaks["hbm_gbps"] * 1e9)
    return max(t_flops, t_bytes), ("flops" if t_flops >= t_bytes else "bytes")
