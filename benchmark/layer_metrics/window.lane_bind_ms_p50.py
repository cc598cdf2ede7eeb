"""Layer lane_window. Median `lane` span that started inside the window: a
session's first call entered the executor (`process`, `start_pos` 0) -> its
lane (mesh: slot) is bound and the session table's lock released: the table's
lock under a drain (the mesh's is its devices' lock too); /spans, host clock
of the node. None on a program that stamps no `lane`."""

import spans


def read(run):
    return spans.median_ms(run, "lane")
