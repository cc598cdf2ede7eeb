"""Layer kv_manager. Bytes a session holds whatever its length: /stats
`executor` `state_bytes_per_session` (the cache's StateEntry buffers, a
Mamba layer's recurrent state and its convolution's kept columns, over the
lanes) at the window's end. It is what packs sessions onto a chip where
keys and values per token would not. Nothing to read where the program
holds no recurrent state."""

import arith


def read(run):
    return arith.dig(run["stats1"], "executor.state_bytes_per_session", None)
