"""Layer kv_manager. Bytes of cache the lanes allocate per token of a
session's budget: /stats `executor` `kv_bytes_per_token` (the cache's
buffers over lanes x max_len) at the window's end. A latent cache holds the
latent and one rope key per layer; keys and values per head would be some
nine times that. Nothing to read where the program does not report it."""

import arith


def read(run):
    return arith.dig(run["stats1"], "executor.kv_bytes_per_token", None)
