"""The finished requests a serving cell compares, drawn from the seed."""
import numpy as np
import pytest

from harness.common import load_module

serve = load_module("kinds", "serve.py")


@pytest.mark.parametrize("seed", [1, 2 ** 33 + 7, 2 ** 31 + 11])
def test_chat_sample_leaves_no_half_of_a_wave_unchecked(seed):
    slots = 32
    pick = serve.checked_requests(3 * slots, slots, 8, seed)
    assert len(pick) == len(set(pick)) == 8
    pos = np.array(pick) % slots
    for half in (pos < slots // 2, pos >= slots // 2, pos % 2 == 0,
                 pos % 2 == 1):
        assert half.any(), pick


def test_rag_sample_covers_every_slot_evenly():
    slots = 4
    pick = serve.checked_requests(38 * slots, slots, 64, 5)
    assert len(set(pick)) == 64
    assert np.bincount(np.array(pick) % slots).tolist() == [16] * 4


def test_sample_depends_on_the_seed_and_fits_a_short_window():
    a = serve.checked_requests(96, 32, 8, 1)
    b = serve.checked_requests(96, 32, 8, 2)
    assert a != b
    assert serve.checked_requests(96, 32, 8, 1) == a
    assert len(serve.checked_requests(4, 4, 8, 3)) == 4
