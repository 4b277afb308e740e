"""The work counted from shapes, against a qwen3-1.7b decode step worked
by hand, and the peak table; the decode roofline's reading."""
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from types import SimpleNamespace  # noqa: E402

from benchkit import cell, counting, engine, harness  # noqa: E402

QWEN = engine.work(cell.load_json(BENCH / "configs" / "qwen3-1.7b.json"))


def test_qwen3_decode_step_by_hand():
    # per layer: q 2048x2048 + k, v 2048x1024 each + o 2048x2048
    #            + gate, up, down 2048x6144 each = 50,331,648
    assert QWEN.layer_params == 4194304 + 2 * 2097152 + 4194304 + 37748736
    # 28 layers + the tied head 151936 x 2048 = 1,720,451,072 parameters
    assert QWEN.layers * QWEN.layer_params + QWEN.head_params == 1720451072
    assert QWEN.weight_bytes == 3440902144
    # 28 layers x (k + v) x 8 heads x 128 x 2 bytes
    assert QWEN.kv_bytes_per_token == 114688
    # one sequence whose new token attends to 1024 keys:
    # 2 x 1,720,451,072 + 28 x 4 x 16 x 128 x 1024 operations, and the
    # weights plus 1024 entries of KV (1023 read, 1 written)
    flops, nbytes = counting.decode_step(QWEN, [1024])
    assert flops == 3440902144 + 234881024
    assert nbytes == 3440902144 + 117440512
    least = counting.least_time(flops, nbytes, counting.peaks("TPU v5 lite"))
    assert least == pytest.approx(3558342656 / 819e9)    # 4.3447 ms
    assert least == pytest.approx(4.3447e-3, rel=1e-4)


def test_batch_reads_weights_once():
    f1, b1 = counting.decode_step(QWEN, [100])
    f2, b2 = counting.decode_step(QWEN, [100, 100])
    assert f2 == 2 * f1
    assert b2 - b1 == 100 * QWEN.kv_bytes_per_token


def test_prefill_chunks_add_up():
    whole = counting.prefill(QWEN, 0, 1000, logits=True)
    parts = (counting.prefill(QWEN, 0, 512, logits=False)
             + counting.prefill(QWEN, 512, 488, logits=True))
    assert whole == parts
    # each position attends to itself and everything before it
    one = counting.prefill(QWEN, 9, 1, logits=False)
    assert one == QWEN.token_flops(10)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        counting.peaks("cpu")
    assert counting.peaks("TPU v5 lite")["flops_bf16"] == 197e12


def test_qwen3_work_counts_are_pinned():
    """The integers the dense counting gave before model families became
    modules of their own."""
    assert QWEN.weight_bytes == 3440902144
    assert QWEN.kv_bytes_per_token == 114688
    assert [QWEN.token_flops(c) for c in (1, 512, 2048)] == [
        2818801664, 2936012800, 3288334336]
    assert QWEN.head_flops == 622329856
    assert QWEN.prefill_flops(0, 512) == 1473232502784
    assert QWEN.prefill_flops(1024, 512) == 1593491587072
    assert counting.prefill(QWEN, 1024, 512, logits=True) == 1594113916928


@pytest.mark.parametrize("chips", [1, 4])
def test_decode_roofline_divides_the_least_time_by_the_chips(chips):
    """The decode work is counted once; its least time on ``chips`` chips
    is the one-chip least time over ``chips``, against the program's
    device time averaged over the chips."""
    reader = cell.load_module(BENCH / "metrics" / "decode_roofline.py")
    peak = counting.peaks("TPU v5 lite")
    work = harness.Work(decode_steps=10)
    for ctx in (100, 2000):
        f, b = counting.decode_step(QWEN, [ctx])
        work.decode_flops += 10 * f
        work.decode_kv_bytes += 10 * (b - QWEN.weight_bytes)
    ctx = SimpleNamespace(
        trace={"program_s": {"serve_scan": 0.5}}, out=SimpleNamespace(
            work=work), shape=QWEN, peak=peak, chips=chips)
    least = max(work.decode_flops / 197e12,
                (10 * 3440902144 + work.decode_kv_bytes) / 819e9)
    assert reader.read(ctx) == pytest.approx(100 * least / chips / 0.5)
