#!/usr/bin/env python3
"""Hold ``BENCHMARK.json`` to the rules of the benchmark's contract, and to
this harness's own: every name in it must lead to a file. Run it before any
chip time is spent:

    python3 benchmarks/check_manifest.py [path/to/BENCHMARK.json]

Prints one line per fault and exits 1 if there is any. Imports neither JAX
nor the engine.
"""

import json
import os
import re
import sys
from typing import Dict, List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
E2E_SOURCES = ("host_clock", "device_trace")
TRAFFIC_SUFFIXES = (".json", ".jsonl", ".toml", ".txt", ".csv")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
MAX_BOUND = 0.25
MAX_RUN_SECONDS = 51
MAX_BYTES = 64 * 1024
# a width may not be reduced; these mark one in a key of ``reduced``
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state_size", "proj",
               "head_dim", "head_size", "expansion", "experts_per_tok")


def line_ok(text, limit: int = 200) -> bool:
    return (isinstance(text, str) and 1 <= len(text) <= limit
            and "\n" not in text and "\t" not in text and "\r" not in text)


def inside_repo(path: str) -> bool:
    return not (path.startswith("/") or ".." in path.split("/"))


def check(manifest: Dict, root: str = ROOT, files: bool = True) -> List[str]:
    """Every fault found, as text; empty when the manifest stands."""
    faults: List[str] = []
    bad = faults.append
    if set(manifest) != TOP_KEYS:
        bad(f"top-level keys must be exactly {sorted(TOP_KEYS)}, not "
            f"{sorted(manifest)}")
        return faults

    paths = manifest["paths"]
    if not (isinstance(paths, list) and 1 <= len(paths) <= 16
            and all(isinstance(p, str) and PATH.match(p) and inside_repo(p)
                    for p in paths)):
        bad("paths: 1 to 16 relative directories of letters, digits, _ . - /")
        paths = []
    command = manifest["command"]
    if not (isinstance(command, list) and 1 <= len(command) <= 32
            and all(line_ok(w) for w in command)):
        bad("command: a list of 1 to 32 one-line strings")
    else:
        for w in command:
            if not inside_repo(w):
                bad(f"command word {w!r} starts with / or leads out through ..")
            elif files and os.path.exists(os.path.join(root, w)) and not any(
                    w == p or w.startswith(p.rstrip("/") + "/") for p in paths):
                bad(f"command names {w!r}, a file of the repo outside paths")
    rs = manifest["run_seconds"]
    if not (isinstance(rs, int) and not isinstance(rs, bool)
            and 1 <= rs <= MAX_RUN_SECONDS):
        bad(f"run_seconds: a whole number from 1 to {MAX_RUN_SECONDS}")

    def under_paths(f: str) -> bool:
        return any(f.startswith(p.rstrip("/") + "/") for p in paths)

    def keys_are(entry: Dict, where: str, required: set, optional=()) -> bool:
        extra = set(entry) - required - set(optional)
        missing = required - set(entry)
        if extra or missing:
            bad(f"{where}: keys must be {sorted(required)}"
                + (f" (+ optional {sorted(optional)})" if optional else "")
                + f"; extra {sorted(extra)}, missing {sorted(missing)}")
            return False
        return True

    def name_ok(value, where: str) -> bool:
        if not (isinstance(value, str) and NAME.match(value)):
            bad(f"{where}: {value!r} must be 1 to 64 characters from letters, "
                "digits, '_', '.' and '-', starting with a letter, digit or '_'")
            return False
        return True

    def unique(names: List[str], what: str) -> None:
        seen = set()
        for n in names:
            if n in seen:
                bad(f"two {what} are named {n!r}")
            seen.add(n)

    # configurations
    configs = manifest["configs"]
    if not (isinstance(configs, list) and 1 <= len(configs) <= 24):
        bad("configs: 1 to 24 entries")
        configs = []
    config_files = []
    for c in configs:
        where = f"config {c.get('name')!r}"
        if not keys_are(c, where, {"name", "source", "file", "reduced", "why"}):
            continue
        name_ok(c["name"], where + " name")
        for key in ("source", "why"):
            if not line_ok(c[key]):
                bad(f"{where}: {key} must be 1 to 200 characters on one line")
        f = c["file"]
        if not (isinstance(f, str) and PATH.match(f) and under_paths(f)):
            bad(f"{where}: file {f!r} must lie under paths")
        elif files and not os.path.isfile(os.path.join(root, f)):
            bad(f"{where}: file {f!r} does not exist")
        config_files.append(f)
        red = c["reduced"]
        if not (isinstance(red, list) and len(red) <= 16):
            bad(f"{where}: reduced is a list of at most 16 keys")
            continue
        for key in red:
            if name_ok(key, where + " reduced key"):
                low = key.lower()
                if (low.endswith("_dim") or low.endswith("_rank")
                        or any(w in low for w in WIDTH_WORDS)):
                    bad(f"{where}: reduced may not name a width: {key!r}")
    unique([c.get("name") for c in configs], "configs")
    unique(config_files, "configs' files")

    # cells
    cells = manifest["workloads"]
    if not (isinstance(cells, list) and 1 <= len(cells) <= 24):
        bad("workloads: 1 to 24 cells")
        cells = []
    config_names = {c.get("name") for c in configs}
    pairs = []
    for w in cells:
        where = f"workload {w.get('name')!r}"
        if not keys_are(w, where, {"name", "config", "traffic", "chips", "why"}):
            continue
        name_ok(w["name"], where + " name")
        name_ok(w["traffic"], where + " traffic")
        if w["config"] not in config_names:
            bad(f"{where}: config {w['config']!r} is not among configs")
        if w["chips"] not in (1, 4) or isinstance(w["chips"], bool):
            bad(f"{where}: chips is 1 or 4")
        if not line_ok(w["why"]):
            bad(f"{where}: why must be 1 to 200 characters on one line")
        pairs.append((w["config"], w["traffic"]))
        if files and isinstance(w["traffic"], str) and not any(
                os.path.isfile(os.path.join(BENCH_DIR, "traffic",
                                            w["traffic"] + s))
                for s in TRAFFIC_SUFFIXES):
            bad(f"{where}: no data file traffic/{w['traffic']}.* for its mix")
    unique([w.get("name") for w in cells], "workloads")
    unique(pairs, "workloads with one pair of config and traffic")
    for c in config_names - {w.get("config") for w in cells}:
        bad(f"config {c!r} is used by no cell")
    four = sum(1 for w in cells if w.get("chips") == 4)
    if four > max(1, len(cells) // 2):
        bad(f"{four} of {len(cells)} cells ask for 4 chips: at most half, "
            "rounded down, and one always")

    # metrics
    cell_names = {w.get("name") for w in cells}
    e2e = manifest["end_to_end"]
    layers = manifest["per_layer"]
    if not (isinstance(e2e, list) and 1 <= len(e2e) <= 16):
        bad("end_to_end: 1 to 16 metrics")
        e2e = []
    if not (isinstance(layers, list) and 1 <= len(layers) <= 128):
        bad("per_layer: 1 to 128 metrics")
        layers = []

    def cells_of(m: Dict) -> set:
        return set(m["workloads"]) if "workloads" in m else set(cell_names)

    def metric_common(m: Dict, where: str, sources) -> None:
        name_ok(m["name"], where + " name")
        if not (isinstance(m["unit"], str) and UNIT.match(m["unit"])):
            bad(f"{where}: unit {m['unit']!r} must be 1 to 16 characters from "
                "letters, digits, '_', '/', '%', '.' and '-'")
        if m["better"] not in ("lower", "higher"):
            bad(f"{where}: better is 'lower' or 'higher'")
        if m["source"] not in sources:
            bad(f"{where}: source must be one of {list(sources)}")
        if "workloads" in m:
            ws = m["workloads"]
            if not (isinstance(ws, list) and ws
                    and all(w in cell_names for w in ws)):
                bad(f"{where}: workloads must list cells of the manifest")
        if files and isinstance(m["name"], str) and not os.path.isfile(
                os.path.join(BENCH_DIR, "metrics", m["name"] + ".py")):
            bad(f"{where}: no reader metrics/{m['name']}.py")

    e2e_cells: Dict[str, set] = {}
    for m in e2e:
        where = f"end_to_end metric {m.get('name')!r}"
        if not keys_are(m, where, {"name", "unit", "better", "bound", "source"},
                        ("workloads",)):
            continue
        metric_common(m, where, E2E_SOURCES)
        b = m["bound"]
        if not (isinstance(b, (int, float)) and not isinstance(b, bool)
                and 0.01 <= b <= MAX_BOUND):
            bad(f"{where}: bound must lie between 0.01 and {MAX_BOUND}")
        e2e_cells[m["name"]] = cells_of(m)
    if "setup_s" not in e2e_cells:
        bad("end_to_end must hold setup_s")
    elif e2e_cells["setup_s"] != cell_names:
        bad("setup_s must be reported in every cell")
    for cell in cell_names:
        if not any(cell in cs for n, cs in e2e_cells.items() if n != "setup_s"):
            bad(f"cell {cell!r} reports no end-to-end metric besides setup_s")
    layer_cells = set()
    for m in layers:
        where = f"per_layer metric {m.get('name')!r}"
        if not keys_are(m, where,
                        {"name", "unit", "better", "source", "layer", "moves"},
                        ("workloads",)):
            continue
        metric_common(m, where, SOURCES)
        if not (isinstance(m["layer"], str) and NAME.match(m["layer"])):
            bad(f"{where}: layer {m['layer']!r} must be 1 to 64 characters "
                "from letters, digits, '_', '.' and '-', starting with a "
                "letter, digit or '_'; no space")
        moved = e2e_cells.get(m["moves"])
        if moved is None:
            bad(f"{where}: moves {m['moves']!r}, which is no end-to-end metric")
        elif not cells_of(m) <= moved:
            bad(f"{where}: moves {m['moves']!r}, which is not reported in "
                f"{sorted(cells_of(m) - moved)}")
        layer_cells |= cells_of(m)
        if re.search(r"_roofline$|mfu", str(m["name"])) and m["unit"] != "%":
            bad(f"{where}: a roofline or mfu share has the unit %")
    for cell in cell_names - layer_cells:
        bad(f"cell {cell!r} reports no per-layer metric")
    unique([m.get("name") for m in e2e + layers], "metrics")

    if files:
        for p in paths:
            for d, _sub, fs in os.walk(os.path.join(root, p)):
                if "__pycache__" in d:
                    continue
                for f in fs:
                    rel = os.path.relpath(os.path.join(d, f), root)
                    if not PATH.match(rel):
                        bad(f"file {rel!r} under paths has a character a "
                            "name may not have")
    return faults


def main(argv: List[str]) -> int:
    path = argv[1] if len(argv) > 1 else os.path.join(ROOT, "BENCHMARK.json")
    with open(path) as f:
        text = f.read()
    if len(text.encode()) > MAX_BYTES:
        print(f"{path}: over {MAX_BYTES} bytes")
        return 1
    faults = check(json.loads(text))
    for fault in faults:
        print("FAULT " + fault)
    print(f"{path}: " + ("ok" if not faults else f"{len(faults)} fault(s)"))
    return 1 if faults else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
