"""The kernels' operation counts at the shapes each call ran."""
import pytest

from bench.lib import roofline
from bench.lib.model import Dims

D = Dims(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
         d_ff=128, vocab=100, qk_norm=True, rope_theta=1e4, rms_eps=1e-6)


def keys_of(pos0, k, n):
    """Keys attended over a scan, step by step: the lane advances k
    times, then stays frozen at its last length."""
    return sum(pos0 + min(s, k) + 1 for s in range(n))


@pytest.mark.parametrize("pos0,steps,n", [
    ([10], [4], 4), ([10, 300], [3, 256], 256), ([0, 7, 7, 7], [1, 0, 0, 0],
                                                 8), ([5], [300], 2048)])
def test_decode_scan_counts_every_lane_and_step(pos0, steps, n):
    ops, _ = roofline.decode_call_cost(D, pos0, steps, n)
    keys = sum(keys_of(p, min(k, n), n) for p, k in zip(pos0, steps))
    assert ops == D.n_layers * roofline.attn_flops(D, keys)


def test_finished_lanes_cost_more_than_the_tokens_they_emit():
    """A 2048-step scan for a lane that needs 300 steps: the kernel is
    handed the frozen steps too, so its work is not the work asked for."""
    ops, _ = roofline.decode_call_cost(D, [100], [300], 2048)
    asked, _ = roofline.decode_call_cost(D, [100], [300], 300)
    assert ops > 6 * asked


def test_prefill_counts_live_lanes_causally():
    ops, nbytes = roofline.prefill_call_cost(D, [0, 0], [8, 0])
    assert ops == D.n_layers * roofline.attn_flops(D, 1) * (8 * 9 // 2)
    # a chunk after a cached history attends the history too
    ops2, _ = roofline.prefill_call_cost(D, [16], [8])
    assert ops2 == D.n_layers * roofline.attn_flops(D, 1) * (
        8 * 16 + 8 * 9 // 2)
    assert roofline.prefill_call_cost(D, [5], [0]) == (0, 0)
    assert nbytes > 0
