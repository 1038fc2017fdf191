"""Operation and byte counts against hand counts at small shapes."""
import pytest

from bench import counts as C

SHAPES = {"n_users": 3, "n_items": 2, "n_edges": 4, "k_users": 2,
          "k_items": 1, "n_hot_users": 2, "dim": 2, "n_layers": 1}


def test_unknown_device_raises():
    with pytest.raises(KeyError, match="no published peaks"):
        C.peaks("TPU v99")
    assert C.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_propagation_by_hand():
    # one layer, d = 2: each of 4 edges scales and adds a 2-wide row on
    # each side (4 * 2 * 2 * 2 = 32), the layer joins the running sum
    # (5 rows * 2 = 10), and the sum is divided once (10)
    assert C.propagation_flops(3, 2, 4, 2, 1) == 32 + 10 + 10


def test_serve_dispatch_by_hand():
    # users 3 x (2 - 1) x 2 = 6 adds to expand; items 1-hot: 0;
    # propagation 52; scoring 1 user x 2 items x 2 x 2 = 8
    assert C.serve_dispatch_flops(SHAPES, 1) == 6 + 0 + 52 + 8


def test_train_step_by_hand():
    # expansion 6 and propagation 52, forward and backward; readout of
    # batch 2: 2 * (10 * 2 + 3) = 46, three times; Adam 14 per entry of
    # (2 + 1) rows x 2
    assert C.train_step_flops(SHAPES, 2) == (2 * 6 + 2 * 52 + 3 * 46
                                             + 14 * 6)


HLO = ("%_codebook_lookup_jit.2 = f32[52648,64]{1,0:T(8,128)} custom-call("
       "s32[105296]{0:T(1024)S(1)} %reshape.0, f32[2046,1,64]{2,1,0:T(1,128)"
       "S(1)} %copy.4), custom_call_target=\"tpu_custom_call\"")


def test_lookup_cost_from_hlo_shapes():
    flops, nbytes = C.lookup_cost(HLO)
    assert flops == (105296 - 52648) * 64
    assert nbytes == 4 * 105296 + 4 * 105296 * 64 + 4 * 52648 * 64
    with pytest.raises(ValueError):
        C.lookup_cost("%fusion.3 = f32[8,8] fusion()")


def test_roofline_takes_the_larger_bound():
    peak = C.peaks("TPU v5 lite")
    assert C.roofline_s(197e12, 0, peak) == pytest.approx(1.0)
    assert C.roofline_s(1.0, 819e9 * 2, peak) == pytest.approx(2.0)
