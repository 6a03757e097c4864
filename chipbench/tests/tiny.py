"""A tiny qwen2 configuration and cells for CPU tests of the harness."""
import json

from chipbench import manifest

CELL = "train-codist2-qwen1.5-0.5b"      # whose limits the tiny runs are held to
ALLREDUCE_CELL = "train-allreduce-qwen1.5-0.5b"

CONFIG = {
    "source": "tiny test configuration", "reference": "qwen2",
    "model_type": "qwen2", "hidden_act": "silu", "qkv_bias": True,
    "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "num_hidden_layers": 2, "rms_norm_eps": 1e-06, "rope_theta": 1000000.0,
    "tie_word_embeddings": True, "vocab_size": 500, "reduced": {},
    "run": {"program_arch": "qwen1.5-0.5b", "param_dtype": "float32",
            "activation_dtype": "bfloat16", "vocab_pad_multiple": 256,
            "remat": True, "fused_losses": False},
}


def bench_and_cell(tmp_path, traffic="codist2", name=CELL, **run):
    cfg = json.loads(json.dumps(CONFIG))
    cfg["run"].update(run)
    path = tmp_path / "tiny.json"
    path.write_text(json.dumps(cfg))
    real = manifest.load()
    bench = dict(real, configs=[{"name": "tiny", "file": str(path)}])
    cell = {"name": name, "config": "tiny", "traffic": traffic, "chips": 1}
    return bench, cell


def shrink(traffic):
    """A traffic file at 32 tokens a row, with as many rows a step as
    ``codist2``'s tiny form: 2 a peer, so 4 for the one all-reduce model."""
    return dict(traffic, batch=4 // int(traffic["models"]), seq=32)


def traffic(name="codist2"):
    return dict(shrink(manifest.traffic(name)), log_every=2)
