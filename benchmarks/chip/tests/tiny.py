"""A configuration and mixes small enough for a CPU test run."""
SPEC = {
    "name": "tiny", "source": "test", "program_config": "h2o-danube-1.8b",
    "family": "llama",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 512, "sliding_window": 4096, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-05, "hidden_act": "silu",
    "tie_word_embeddings": False, "torch_dtype": "bfloat16", "reduced": [],
    "deployment": {"chips": 1, "max_len": 256, "max_active": 4,
                   "arena_pages": 33, "prefix_cache_pages": 33},
}
BACKLOG = {
    "kind": "backlog", "sizes_seed": 3, "prefixes": 8, "prefix_len_mean": 20,
    "prefix_len_sd": 4, "prefix_zipf": 0.8, "suffix_len_mean": 60,
    "suffix_len_sd": 18, "max_new": 40, "max_context": 256, "requests": 64,
}
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
