"""Ask the chip's compiler, without the chip (test_chip_compile.py), the
configuration FILES of the models that keep a convolution window, at their
published widths: one AOT compile of both step programs a file.
"""

import jax
import pytest

from chip_compile import _file_model, _step_hlo_copies
from ollamamq_tpu.config import LINEAR
from ollamamq_tpu.models import llama


@pytest.mark.parametrize("name", [
    "olmo-hybrid-7b-d16", "lfm2-8b-a1b-d18", "qwen3-next-80b-a3b-ep4-d12"])
def test_no_step_program_copies_the_conv_window(v5e, capsys, name):
    """The three configuration files whose models keep a convolution window
    (PR 53), at PUBLISHED widths and a 64-token ragged step (their `rehearse`
    sizes list no program here: PERF.md section 7; ~35 s a file): the window
    is stored a tap a plane, [layers, K-1, slots, D], and neither step
    program holds a `copy` of its shape, whole or a layer's — stored a slot
    a sliver, Olmo-Hybrid's ragged step opened and closed with a copy of all
    54 MB and its decode scan re-laid a layer's 4.4 MB twice a layer. (An
    in-place `dynamic-update-slice` fusion keeps the window's shape for its
    result and is no copy; the one READ of a layer's planes is a
    `dynamic-slice`.) And by the compiler's own estimate (`--by-scope`) a
    linear layer's `lin_conv` stage — 4.4 MB of window — costs under two
    thirds of its `lin_in`, which streams 132 MB of weights: it was costed
    ABOVE it."""
    programs, _ = _step_hlo_copies(capsys, name, "--tokens", "64",
                                   "--min-mb", "0.25", "--by-scope")
    assert [p["program"] for p in programs] \
        == ["mq_ragged_step", "mq_decode_scan"]
    cfg, mc = _file_model(name)
    slots = int(cfg["server_flags"][cfg["server_flags"].index("--max-slots")
                                    + 1])
    window = jax.eval_shape(
        lambda: llama.alloc_slot_state(mc, slots)).conv.shape
    assert window[1:3] == (mc.state_window[0] - 1, slots), window
    for p in programs:
        copies = [m for m in p["moves"] if m["moves"] == "copy"
                  and tuple(d for d in m["dims"] if d != 1)
                  in (tuple(window), tuple(window[1:]))]
        assert not copies, (p["program"], copies)
        if mc.count(LINEAR):  # the computation of a period of the layers
            period = max(p["scope_cycles"].values(),
                         key=lambda by: by.get("lin_in", [0])[0])
            assert 0 < period["lin_conv"][0] * 1.5 < period["lin_in"][0], \
                (p["program"], period)
