"""FLOP and byte counts by hand arithmetic at small shapes, and the
table of peaks."""
import pytest

from bench import harness, spec as bspec

XS = bspec.load_module(bspec.config_module_path("xsilo-gisette-n20"),
                       "xsilo_counts")
M2 = bspec.load_module(bspec.config_module_path("mamba2-1.3b-l16"),
                       "mamba2_counts")


def test_xsilo_round_counts_by_hand():
    # n=2 clients, r=3 rows, d=4, m=2, one grid point
    flecs = XS.round_counts("flecs_cgd", 1, n=2, r=3, d=4, m=2)
    data = 4 * 2 * 3 * 4 * 4                 # four passes over A: 384 B
    B_passes = 3 * 2 * 4 * 4 * 4             # three over B [2, 4, 4]: 384 B
    assert flecs["bytes"] == data + B_passes == 768
    assert flecs["flops"] == (
        96 + 144                     # gradient 4nrd, recorded F and ∇F 6nrd
        + 192 + 64                   # HVPs 4nrdm, M = SᵀY 2ndm²
        + 128                        # B·S 2nd²m
        + 64 + 128                   # Ỹ M† Ỹᵀ
        + 96)                        # B update 3nd²
    diana = XS.round_counts("diana", 2, n=2, r=3, d=4, m=0)
    assert diana == {"flops": 2 * 240, "bytes": 2 * 384}


def test_xsilo_kernel_elements_by_hand():
    assert XS.kernel_elements("flecs_cgd", 2, n=3, d=4, m=2) == {
        "dither": 2 * (12 + 24), "topk": 2 * (12 + 24)}
    assert XS.kernel_elements("diana", 2, n=3, d=4, m=0) == {
        "dither": 24, "topk": 24}


def test_xsilo_full_size_round_is_bytes_bound():
    c = XS.round_counts("flecs_cgd", 2, n=20, r=300, d=5000, m=8)
    assert c["bytes"] == 2 * (3 * 20 * 5000 ** 2 * 4 + 4 * 20 * 300 * 5000 * 4)
    peaks = harness.peaks_for("TPU v5 lite")
    assert (c["bytes"] / peaks["hbm_bytes_per_s"]
            > c["flops"] / peaks["bf16_flops_per_s"])


def test_mamba2_flops_per_token_by_hand():
    c = {"d_model": 4, "expand": 2, "headdim": 2, "d_state": 3,
         "chunk_size": 5, "d_conv": 2, "n_layer": 2, "vocab_size": 7}
    # d_inner 8, heads 4; per layer: in_z, in_x 4·8 each, in_B, in_C 4·3
    # each, in_dt 4·4, out_proj 8·4, convs 2·(8 + 3 + 3)
    per_layer = 32 + 32 + 12 + 12 + 16 + 32 + 28
    matmul_params = 2 * per_layer + 4 * 7
    ssd = 2 * 5 * 3 + 2 * 5 * 4 * 2 + 4 * 4 * 2 * 3
    assert M2.flops_per_token(c) == 6 * matmul_params + 3 * 2 * ssd


def test_mamba2_published_flops_per_token():
    c = bspec.read_json(bspec.ROOT / "bench/configs/mamba2-1.3b-l16.json")
    f = M2.flops_per_token(c)
    assert 3.0e9 < f < 3.5e9


def test_peaks_table_and_unknown_kind():
    p = harness.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        harness.peaks_for("cpu")
