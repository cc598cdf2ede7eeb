"""Layer kv_manager. Bytes a session's windowed layers hold whatever its
length: /stats `executor` `kv.ring_bytes_per_session` (the cache's RingEntry
buffers, keys and values of `kv.window` tokens rounded up and a margin a
windowed layer, over the lanes) at the window's end. Full-length slabs for
those layers would grow with --max-len. Nothing to read where the program
keeps no ring."""

import arith


def read(run):
    return arith.dig(run["stats1"], "executor.kv.ring_bytes_per_session", None)
