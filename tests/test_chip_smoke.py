"""chip_smoke.py's contract, rehearsed without the chip.

PR 21 was lost to the script's last line, not to the program, so that line
has a test: `--rehearse` drives every phase at the `tiny` preset on the CPU
backend (the on-chip-measurement guide's first rehearsal) and must end in
exactly the contract's JSON object with `"ok": false`, because the node
reports `cpu` and not `tpu`. The option is never the default and can only
end in `"ok": false`.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402  (importing it redirects nothing: see claim_stdout)

ONE_CHIP_CHECKS = [
    "node_reports_tpu", "warmup_ok", "solo_well_formed", "repeat_same_tokens",
    "repeat_compiles_nothing", "concurrent_well_formed", "cobatch_same_tokens",
    "sessions_resident_together", "server_side_same_tokens",
    "server_side_compiles_nothing", "no_warmup_failure_later", "node_exit",
    "plain_engine_first_token", "parent_held_no_backend",
]
FOUR_CHIP_CHECKS = [c for c in ONE_CHIP_CHECKS if c != "plain_engine_first_token"]
FOUR_CHIP_CHECKS[-1:-1] = [
    "four_devices", "every_device_holds_its_share",
    "mesh_first_token_is_stagewise_argmax",
]


def _rehearse(*args):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), "--rehearse", *args],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=420,
    )
    lines = r.stdout.splitlines()
    checks = {}
    for line in lines[:-1]:
        word, _, rest = line.removeprefix("[smoke] ").partition(" ")
        if word in ("PASS", "FAIL"):
            checks[rest.split(":")[0]] = word == "PASS"
    return r, lines, checks


@pytest.mark.parametrize("args,count,expected,may_fail", [
    ((), 1, ONE_CHIP_CHECKS, {"node_reports_tpu"}),
    # memory_stats() is a TPU facility: on virtual CPU devices the share
    # check runs and finds nothing to read
    (("--chips", "4"), 4, FOUR_CHIP_CHECKS,
     {"node_reports_tpu", "every_device_holds_its_share"}),
], ids=["one-chip", "four-chips"])
def test_rehearsal_ends_in_the_contracts_last_line(args, count, expected,
                                                   may_fail):
    r, lines, checks = _rehearse(*args)
    tail = r.stdout[-2000:] + r.stderr[-2000:]
    assert r.returncode != 0, tail
    last = json.loads(lines[-1])
    assert set(last) == {"ok", "device"}, lines[-1]
    assert set(last["device"]) == {"platform", "kind", "count"}, lines[-1]
    assert last["ok"] is False
    assert last["device"] == {"platform": "cpu", "kind": "cpu", "count": count}
    # every phase ran, in order, and none was skipped to get to the end;
    # what failed is exactly what a CPU cannot satisfy
    assert list(checks) == expected, tail
    assert {c for c, ok in checks.items() if not ok} == may_fail, tail


def test_last_line_has_the_contracts_keys_and_no_others():
    line = chip_smoke.last_line(
        True, {"platform": "tpu", "device_kind": "TPU v5 lite",
               "device_count": 1, "memory": [{"bytes_in_use": 1}]},
    )
    assert line == (
        '{"ok": true, "device": {"platform": "tpu", '
        '"kind": "TPU v5 lite", "count": 1}}'
    )
    # before any node has reported: the same shape, nothing invented
    assert json.loads(chip_smoke.last_line(False, {})) == {
        "ok": False, "device": {"platform": None, "kind": None, "count": 0},
    }


def test_a_directory_without_the_repo_fails_in_the_contracts_shape(tmp_path):
    """The script alone, without the program: non-zero, `"ok": false`."""
    import shutil

    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode != 0
    last = json.loads(r.stdout.splitlines()[-1])
    assert last == {
        "ok": False, "device": {"platform": None, "kind": None, "count": 0},
    }


@pytest.mark.parametrize("used,ok", [
    ([7.2e9, 7.2e9, 7.2e9, 7.2e9], True),   # each rank: layers + heads + KV
    ([16.4e9, 0, 0, 0], False),             # everything on the first chip
    ([7.2e9, 7.2e9, 7.2e9, 1.0e9], False),  # a rank without its layers
], ids=["shared", "all-on-first", "missing-slice"])
def test_memory_share_rule(used, ok):
    memory = [{"bytes_in_use": int(u)} for u in used]
    got, _detail = chip_smoke.memory_shares_ok(
        memory, weight_bytes=int(16.4e9), layer_share=int(3.4e9)
    )
    assert got is ok
    assert chip_smoke.memory_shares_ok([], int(16.4e9), int(3.4e9))[0] is False
