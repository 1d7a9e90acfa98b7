import jax.numpy as jnp
import pytest

from chipbench import flops


@pytest.mark.parametrize("tokens", [1, 16, 57, 128, 256, 512])
def test_encoder_flops_equal_the_programs(tokens):
    from pathway_tpu.ops.encoder import EncoderConfig, encoder_flops_per_doc

    config = {"hidden_size": 384, "intermediate_size": 1536, "num_hidden_layers": 6}
    cfg = EncoderConfig(vocab_size=30522, d_model=384, n_heads=12, n_layers=6, d_ff=1536, dtype=jnp.bfloat16, arch="bert")
    assert flops.encoder_flops(config, tokens) == encoder_flops_per_doc(cfg, tokens)


def test_unknown_device_kind_is_an_error():
    assert flops.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        flops.peaks("_source")


def test_roofline_says_which_bound():
    peak = flops.peaks("TPU v5 lite")
    pct, bound = flops.roofline_pct(flops.scan_flops(8, 3_000_000, 384), flops.scan_bytes(3_000_000, 384), 0.03, peak)
    assert bound == "memory" and 0 < pct < 100
    pct, bound = flops.roofline_pct(1e12, 1e6, 0.01, peak)
    assert bound == "compute" and 0 < pct < 100


def test_token_counts_follow_the_tokenizer():
    from chipbench import reference

    text = " ".join(f"w{i}" for i in range(300))
    assert flops.text_tokens(56, 512) == len(reference.text_ids(" ".join(["w1"] * 56), 30522, 512)) == 57
    assert flops.pair_tokens(7, 300, 512) == len(reference.pair_ids("a b c d e f g", text, 30522, 512))
    assert flops.pair_tokens(400, 400, 512) == len(reference.pair_ids(text + " " + text, text + " " + text, 30522, 512)) == 512
