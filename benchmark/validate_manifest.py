#!/usr/bin/env python3
"""Check BENCHMARK.json against every rule of form the benchmark's contract
states, before a chip call and before handing in.

    python3 benchmark/validate_manifest.py [BENCHMARK.json]

Exit code 0 and "ok", or 1 and one line per fault. The ledger's lesson of
PR 23 is rule L below: a per-layer metric's `layer` is an identifier, not a
few plain words.
"""

from __future__ import annotations

import json
import os
import re
import sys

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "projection", "head_dim",
               "head_size", "expansion", "experts_per_tok")
DATA_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")
MAX_BOUND, MAX_SETUP_BOUND = 0.1, 0.1
MAX_BYTES = 64 * 1024


def text(value, limit=200) -> bool:
    return (isinstance(value, str) and 1 <= len(value) <= limit
            and "\n" not in value and "\t" not in value and "\r" not in value)


def inside(path: str, roots) -> bool:
    return any(path == r or path.startswith(r.rstrip("/") + "/") for r in roots)


def escapes(path: str) -> bool:
    return path.startswith("/") or ".." in path.split("/")


def validate(manifest: dict, root: str, raw_bytes: int = 0) -> list:
    """Every fault found, as text; empty when the manifest is well formed.
    `root` is the checkout: the files a cell names have to be there."""
    bad = []
    say = bad.append
    if raw_bytes > MAX_BYTES:
        say(f"the file is {raw_bytes} bytes, over {MAX_BYTES}")
    if set(manifest) != TOP_KEYS:
        say(f"top-level keys must be exactly {sorted(TOP_KEYS)}, not {sorted(manifest)}")
        return bad

    paths, command = manifest["paths"], manifest["command"]
    if not (isinstance(paths, list) and 1 <= len(paths) <= 16
            and all(isinstance(p, str) and PATH.match(p) and not escapes(p) for p in paths)):
        say("paths: 1 to 16 relative directories of letters, digits, '_', '.', '-', '/'")
        paths = []
    if not (isinstance(command, list) and 1 <= len(command) <= 32
            and all(text(w) for w in command)):
        say("command: a list of 1 to 32 words of 1 to 200 characters")
    else:
        for w in command:
            if escapes(w):
                say(f"command word {w!r} starts with '/' or leads out through '..'")
            elif os.path.exists(os.path.join(root, w)) and not inside(w, paths):
                say(f"command names {w!r}, a file of the repo outside paths")
    rs = manifest["run_seconds"]
    if not (isinstance(rs, int) and not isinstance(rs, bool) and 1 <= rs <= 51):
        say(f"run_seconds must be a whole number from 1 to 51, not {rs!r}")

    def names(entries, what, keys, optional=()):
        seen = set()
        for e in entries:
            if not isinstance(e, dict):
                say(f"{what}: an entry is no object")
                continue
            extra = set(e) - set(keys) - set(optional)
            missing = set(keys) - set(e)
            label = f"{what} {e.get('name')!r}"
            if extra or missing:
                say(f"{label}: keys must be {sorted(keys)}"
                    + (f" (+ {sorted(optional)})" if optional else "")
                    + (f"; unknown {sorted(extra)}" if extra else "")
                    + (f"; missing {sorted(missing)}" if missing else ""))
            if not (isinstance(e.get("name"), str) and NAME.match(e["name"])):
                say(f"{label}: a name is 1 to 64 letters, digits, '_', '.', '-', "
                    "starting with a letter, digit or '_'")
            if e.get("name") in seen:
                say(f"{label}: the name appears twice")
            seen.add(e.get("name"))
        return seen

    # -- configurations ------------------------------------------------------
    configs = manifest["configs"]
    if not (isinstance(configs, list) and 1 <= len(configs) <= 24):
        say("configs: 1 to 24 entries")
        configs = []
    config_names = names(configs, "config", {"name", "source", "file", "reduced", "why"})
    files = set()
    for c in (c for c in configs if isinstance(c, dict)):
        label = f"config {c.get('name')!r}"
        for key in ("source", "why"):
            if not text(c.get(key)):
                say(f"{label}: {key} must be 1 to 200 characters on one line")
        f = c.get("file")
        if not (isinstance(f, str) and PATH.match(f) and inside(f, paths)):
            say(f"{label}: file {f!r} must lie under paths")
        elif f in files:
            say(f"{label}: file {f!r} is another configuration's file too")
        elif not os.path.isfile(os.path.join(root, f)):
            say(f"{label}: file {f!r} is missing")
        else:
            try:
                with open(os.path.join(root, f)) as fh:
                    if not isinstance(json.load(fh), dict):
                        say(f"{label}: file {f!r} is no JSON object")
            except ValueError as e:
                say(f"{label}: file {f!r} is no JSON: {e}")
        files.add(f)
        red = c.get("reduced")
        if not (isinstance(red, list) and len(red) <= 16
                and all(isinstance(k, str) and NAME.match(k) for k in red)):
            say(f"{label}: reduced is a list of at most 16 names")
        else:
            for k in red:
                counts_layers = k.startswith("num_") and k.endswith("_layers")  # num_hidden_layers
                if not counts_layers and (k.endswith("_dim") or k.endswith("_rank")
                                          or any(wd in k for wd in WIDTH_WORDS)):
                    say(f"{label}: reduced names the width {k!r}")

    # -- cells ---------------------------------------------------------------
    cells = manifest["workloads"]
    if not (isinstance(cells, list) and 1 <= len(cells) <= 24):
        say("workloads: 1 to 24 cells")
        cells = []
    cell_names = names(cells, "workload", {"name", "config", "traffic", "chips", "why"})
    pairs, used = set(), set()
    for w in (w for w in cells if isinstance(w, dict)):
        label = f"workload {w.get('name')!r}"
        if w.get("config") not in config_names:
            say(f"{label}: config {w.get('config')!r} is not in configs")
        used.add(w.get("config"))
        if not (isinstance(w.get("traffic"), str) and NAME.match(w["traffic"])):
            say(f"{label}: traffic must be a name")
        elif paths and not any(
            os.path.isfile(os.path.join(root, p, "traffic", w["traffic"] + sfx))
            for p in paths for sfx in DATA_SUFFIXES
        ):
            say(f"{label}: no data file traffic/{w['traffic']}.* under paths")
        if w.get("chips") not in (1, 4):
            say(f"{label}: chips must be 1 or 4")
        if not text(w.get("why")):
            say(f"{label}: why must be 1 to 200 characters on one line")
        if (w.get("config"), w.get("traffic")) in pairs:
            say(f"{label}: this pair of configuration and traffic appears twice")
        pairs.add((w.get("config"), w.get("traffic")))
    for c in config_names - used:
        say(f"config {c!r} is used by no cell")
    four = sum(1 for w in cells if isinstance(w, dict) and w.get("chips") == 4)
    if four > max(1, len(cells) // 4):
        say(f"{four} cells ask for 4 chips; at most {max(1, len(cells) // 4)} may")

    # -- metrics -------------------------------------------------------------
    e2e, per = manifest["end_to_end"], manifest["per_layer"]
    if not (isinstance(e2e, list) and 1 <= len(e2e) <= 16):
        say("end_to_end: 1 to 16 metrics")
        e2e = []
    if not (isinstance(per, list) and 1 <= len(per) <= 128):
        say("per_layer: 1 to 128 metrics")
        per = []
    base = {"name", "unit", "better", "source"}
    e2e_names = names(e2e, "end_to_end metric", base | {"bound"}, {"workloads"})
    per_names = names(per, "per_layer metric", base | {"layer", "moves"}, {"workloads"})
    for n in e2e_names & per_names:
        say(f"metric {n!r} is both end-to-end and per-layer")

    def cells_of(m):
        return set(m["workloads"]) if "workloads" in m else set(cell_names)

    for m in (m for m in e2e + per if isinstance(m, dict)):
        label = f"metric {m.get('name')!r}"
        if not (isinstance(m.get("unit"), str) and UNIT.match(m["unit"])):
            say(f"{label}: unit {m.get('unit')!r} must be 1 to 16 letters, digits, "
                "'_', '/', '%', '.', '-'")
        if m.get("better") not in ("lower", "higher"):
            say(f"{label}: better must be 'lower' or 'higher'")
        if m.get("source") not in SOURCES:
            say(f"{label}: source must be one of {sorted(SOURCES)}")
        if "workloads" in m:
            ws = m["workloads"]
            if not (isinstance(ws, list) and ws and set(ws) <= cell_names):
                say(f"{label}: workloads must list cells of this file")
    for m in (m for m in e2e if isinstance(m, dict)):
        label = f"end_to_end metric {m.get('name')!r}"
        b = m.get("bound")
        limit = MAX_SETUP_BOUND if m.get("name") == "setup_s" else MAX_BOUND
        if not (isinstance(b, (int, float)) and not isinstance(b, bool) and 0 < b <= limit):
            say(f"{label}: bound {b!r} must be a share above 0 and at most {limit}")
        if m.get("source") not in ("host_clock", "device_trace"):
            say(f"{label}: an end-to-end metric is read by host_clock or device_trace")
    setup = next((m for m in e2e if isinstance(m, dict) and m.get("name") == "setup_s"), None)
    if setup is None:
        say("end_to_end must hold setup_s")
    elif "workloads" in setup:
        say("setup_s is reported by every cell: it may list no workloads")
    e2e_by_name = {m["name"]: m for m in e2e if isinstance(m, dict) and "name" in m}
    for m in (m for m in per if isinstance(m, dict)):
        label = f"per_layer metric {m.get('name')!r}"
        layer = m.get("layer")
        if not (isinstance(layer, str) and NAME.match(layer)):  # rule L (ledger, PR 23)
            say(f"{label}: layer must be 1 to 64 characters from letters, digits, '_', "
                f"'.' and '-', starting with a letter, digit or '_', not {layer!r}")
        target = e2e_by_name.get(m.get("moves"))
        if target is None:
            say(f"{label}: moves {m.get('moves')!r} is no end-to-end metric")
        elif isinstance(m.get("workloads", []), list):
            lacking = sorted(c for c in cells_of(m) & cell_names if c not in cells_of(target))
            if lacking:
                say(f"{label}: moves {m['moves']!r}, which cells {lacking} do not report")
    for cell in sorted(cell_names):
        mine = [m for m in e2e if isinstance(m, dict) and cell in cells_of(m)]
        if not any(m.get("name") != "setup_s" for m in mine):
            say(f"workload {cell!r} reports no end-to-end metric besides setup_s")
        if not any(isinstance(m, dict) and cell in cells_of(m) for m in per):
            say(f"workload {cell!r} reports no per-layer metric")

    # a per-layer metric has its reader, found by name
    for m in (m for m in per if isinstance(m, dict) and isinstance(m.get("name"), str)):
        if paths and not any(
            os.path.isfile(os.path.join(root, p, "layer_metrics", m["name"] + ".py"))
            for p in paths
        ):
            say(f"per_layer metric {m['name']!r}: no reader layer_metrics/{m['name']}.py")
    return bad


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    path = argv[0] if argv else os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "BENCHMARK.json")
    with open(path, "rb") as f:
        raw = f.read()
    try:
        manifest = json.loads(raw)
    except ValueError as e:
        print(f"{path}: no JSON: {e}")
        return 1
    if not isinstance(manifest, dict):
        print(f"{path}: no JSON object")
        return 1
    faults = validate(manifest, os.path.dirname(os.path.abspath(path)), len(raw))
    for line in faults:
        print(line)
    print("ok" if not faults else f"{len(faults)} faults")
    return 1 if faults else 0


if __name__ == "__main__":
    sys.exit(main())
