"""Layer lane_window. How long a prefill chunk keeps every drain out: the
median `held_ms` (lock acquired -> released: the chunk's dispatch; on the mesh
the whole pass) of the `lock_wait` spans of kind `prefill` that started inside
the window; /spans, host clock of the node. None on a program whose
`lock_wait` carries no `held_ms`."""

import arith
import spans


def read(run):
    return arith.percentile(
        [s["attrs"]["held_ms"] for s in spans.named(run, "lock_wait", kind="prefill")
         if "held_ms" in s["attrs"]], 50)
