"""What a /forward payload asks, by name (runtime/executor.py: call_kind)."""

import numpy as np
import pytest

from inferd_tpu.runtime import executor as execlib


@pytest.mark.parametrize("payload, kind", [
    ({"tokens": [[5]], "start_pos": 4}, "decode"),
    ({"hidden": np.zeros((1, 1, 8)), "start_pos": 4, "real_len": 1}, "decode"),  # a relayed stage's hop
    ({"tokens": [[5]], "start_pos": 0}, "prefill"),  # one token, no frontier yet
    ({"tokens": [[5, 6, 0, 0]], "start_pos": 4, "real_len": 1}, "decode"),  # `real_len` over the bucket
    ({"tokens": [[5, 6, 7, 8]], "start_pos": 4, "block": {"known": 0}}, "block"),
    ({"tokens": [[5, 6]], "start_pos": 4}, "prefill"),
    ({"tokens": 5, "start_pos": 4}, "prefill"),  # malformed: fails in the guarded compute, not here
    (None, "prefill"),
], ids=["token", "hidden", "first_token", "padded", "block", "chunk", "malformed", "no_payload"])
def test_a_call_is_named_by_what_it_asks(payload, kind):
    assert execlib.call_kind(payload) == kind
