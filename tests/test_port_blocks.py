"""The one allocator of the suite's ports (tests/conftest.py: port_block)."""

import os
import re

import pytest

from conftest import Ports, port_block

HERE = os.path.dirname(os.path.abspath(__file__))
FILES = sorted(f for f in os.listdir(HERE) if f.startswith("test_") and f.endswith(".py"))


def test_no_two_modules_get_overlapping_blocks_whichever_worker_runs_them(monkeypatch):
    seen = None
    for worker in (None, "gw0", "gw5"):  # `--dist loadfile`: a module runs in ONE worker, any of them
        if worker is None:
            monkeypatch.delenv("PYTEST_XDIST_WORKER", raising=False)
        else:
            monkeypatch.setenv("PYTEST_XDIST_WORKER", worker)
        bases = {f: port_block(os.path.join(HERE, f)).base for f in FILES}
        assert seen in (None, bases)
        seen = bases
    spans = sorted((b, b + Ports.WIDTH) for b in seen.values())
    assert all(end <= start for (_, end), (start, _) in zip(spans, spans[1:]))
    assert spans[0][0] >= 1024 and spans[-1][1] <= 32768  # under the ephemeral range
    # every port a module can ask for lies inside its block, HTTP and gossip
    mine = port_block(__file__)
    asked = [mine.http(i) for i in range(Ports.HTTP_SLOTS)] + [mine.gossip(i) for i in range(Ports.HTTP_SLOTS)]
    assert min(asked) == mine.base and max(asked) == mine.base + Ports.WIDTH - 1
    assert mine.gossip(7) != mine.http(7)
    with pytest.raises(AssertionError):
        mine.http(Ports.HTTP_SLOTS)


def test_a_helper_borrowed_from_another_module_binds_the_borrowers_ports():
    """test_faults failed the driver's PR 58 run on test_node_e2e's block:
    `_mk_node` takes the block of the module that calls it."""
    import test_faults
    import test_node_e2e

    assert test_faults.PORTS.base != test_node_e2e.PORTS.base
    assert test_faults._mk_node.keywords == {"ports": test_faults.PORTS}
    node = test_faults._mk_node(70, 0, 1)
    assert node.info.port == test_faults.PORTS.http(70)
    assert node.dht.port == test_faults.PORTS.gossip(70)


def test_no_module_keeps_a_port_constant_of_its_own():
    kept = re.compile(r"^\s*BASE\b.*=|\(\s*\"127\.0\.0\.1\"\s*,\s*\d{4,5}\s*\)", re.M)
    for f in FILES:
        with open(os.path.join(HERE, f)) as fh:
            found = kept.findall(fh.read()) if f != os.path.basename(__file__) else []
        assert not found, (f, found)
