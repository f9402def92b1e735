"""Chip-benchmark cells cut to CPU size for the tests: the ``yi6b-chat``
cell's harness, check and limit over a smoke-width model in bfloat16,
and over the published depth and vocabulary at a narrow width."""
import dataclasses

from benchmarks.chip import harness


def spec_of(cfg) -> dict:
    """The configuration file a smoke config would have."""
    return {"name": cfg.name, "arch": cfg.name,
            "num_hidden_layers": cfg.n_layers, "hidden_size": cfg.d_model,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads,
            "head_dim": cfg.resolved_head_dim,
            "intermediate_size": cfg.d_ff, "vocab_size": cfg.vocab_size,
            "rope_theta": cfg.rope_theta, "hidden_act": cfg.act,
            "gated_mlp": cfg.gated_mlp, "use_bias": cfg.use_bias,
            "tie_word_embeddings": cfg.tie_embeddings,
            "torch_dtype": cfg.param_dtype, "norm_eps": cfg.norm_eps,
            "mesh": None}


def smoke_cell(arch: str = "yi-6b", cell: str = "yi6b-chat"):
    from repro.configs import smoke_config

    cfg = dataclasses.replace(smoke_config(arch), param_dtype="bfloat16")
    c = harness.load_cell(cell)
    c.spec = spec_of(cfg)
    c.traffic = {
        "loop": "open", "rate_per_s": 6.0,
        "prompt_len": {"dist": "lognormal", "median": 24, "sigma": 0.8,
                       "min": 4, "max": 48},
        "output_len": {"dist": "lognormal", "median": 8, "sigma": 0.8,
                       "min": 2, "max": 16},
        "engine": {"max_slots": 4, "max_seq": 64, "page_size": 16},
        "check": {"requests": 4, "block": 2,
                  "gap_limit": c.traffic["check"]["gap_limit"]}}
    return c, cfg


def deep_cell():
    """The cell at yi-6b's depth (32 layers) and vocabulary (64000) but a
    width of 256: rounding compounds over the layers and ranks among the
    vocabulary as at the published size, so the check separates the
    program from the control as it does on the chip."""
    c, cfg = smoke_cell()
    cfg = dataclasses.replace(cfg, n_layers=32, d_model=256, n_heads=8,
                              n_kv_heads=2, head_dim=32, d_ff=704,
                              vocab_size=64000)
    c.spec = spec_of(cfg)
    c.traffic = dict(
        c.traffic, rate_per_s=3.0,
        prompt_len={"dist": "lognormal", "median": 48, "sigma": 0.5,
                    "min": 16, "max": 96},
        output_len={"dist": "uniform", "min": 24, "max": 32},
        engine={"max_slots": 4, "max_seq": 128, "page_size": 16},
        check=dict(c.traffic["check"], requests=4, block=4))
    return c, cfg
