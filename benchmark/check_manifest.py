#!/usr/bin/env python3
"""check_manifest.py — is `BENCHMARK.json` one the driver will take?

The driver refuses a manifest outside its schema *before any run*, and a
refused manifest loses the PR (PR 22 lost four cells to one space in a
`layer`).  So the schema lives here as code, `run.py` calls it at the
start of every run, and a tier-1 test runs it on the real file and on bad
twins.  Every message names the field it is about.

    python benchmark/check_manifest.py [BENCHMARK.json]

`check(manifest, root)` returns a list of messages; empty means good.
Beyond the driver's schema it checks that the benchmark's own data files
say the same as the manifest: each cell's workload, configuration,
traffic and job files exist and agree with its entry, and each per-layer
metric has a file under `layer_metrics/` with the same layer, unit,
direction, source, `moves` and cells.
"""

from __future__ import annotations

import json
import os
import re
import sys

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
FILE_CHARS = re.compile(r"^[A-Za-z0-9_.\-/]+$")
TOP_KEYS = ("command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer")
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
CELL_KEYS = {"name", "config", "traffic", "chips", "why"}
E2E_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}
E2E_SOURCES = ("host_clock", "device_trace")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
MAX_BOUND = 0.1
MIN_BOUND = 0.01
MAX_RUN_SECONDS = 51
MAX_BYTES = 64 * 1024
# a key of `reduced` may never be a width
WIDTH = re.compile(
    r"(_dim$|_rank$|hidden|intermediate|latent|state_size|d_state|"
    r"projection|proj_size|head_size|head_dim|expansion|expand|"
    r"experts_per_tok|top_k|d_model|d_ff|ffn)", re.I)
HERE = os.path.dirname(os.path.abspath(__file__))


def _line(s, lo=1, hi=200) -> bool:
    return (isinstance(s, str) and lo <= len(s) <= hi
            and "\n" not in s and "\r" not in s and "\t" not in s)


def _inside(path: str, roots) -> bool:
    p = os.path.normpath(path)
    return any(p == r or p.startswith(r + "/")
               for r in (os.path.normpath(x) for x in roots))


def _load(path: str, errs: list, what: str):
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        errs.append(f"{what}: cannot read {path}: {e}")
        return None


def _entries(m, key, lo, hi, errs) -> list:
    v = m.get(key)
    if not isinstance(v, list) or not lo <= len(v) <= hi:
        errs.append(f"{key}: must be a list of {lo} to {hi} entries")
        return []
    good = [e for e in v if isinstance(e, dict)]
    if len(good) != len(v):
        errs.append(f"{key}: every entry must be an object")
    return good


def _keys(entry, want, optional, where, errs) -> None:
    got = set(entry)
    if got - want - optional or want - got:
        errs.append(f"{where}: keys must be {sorted(want)}"
                    f"{' plus optional ' + str(sorted(optional)) if optional else ''}"
                    f", got {sorted(got)}")


def _name(v, where, errs) -> bool:
    if not (isinstance(v, str) and NAME.match(v)):
        errs.append(f"{where}: must be 1 to 64 characters from letters, "
                    f"digits, '_', '.' and '-', starting with a letter, "
                    f"digit or '_', not {v!r}")
        return False
    return True


def _unique(names, where, errs) -> None:
    seen = set()
    for n in names:
        if n in seen:
            errs.append(f"{where}: {n!r} appears twice")
        seen.add(n)


def check(m, root: str) -> list:
    """Every reason the driver (or this benchmark) would refuse `m`,
    read against the files under `root`."""
    errs: list = []
    if not isinstance(m, dict):
        return ["manifest: must be a JSON object"]
    if set(m) != set(TOP_KEYS):
        errs.append(f"manifest: keys must be exactly {list(TOP_KEYS)}, "
                    f"got {sorted(m)}")

    # ---- paths, command, run_seconds
    paths = m.get("paths")
    if not (isinstance(paths, list) and 1 <= len(paths) <= 16):
        errs.append("paths: must be a list of 1 to 16 directories")
        paths = []
    for p in paths:
        if not (isinstance(p, str) and PATH.match(p)) or p.startswith("/") \
                or ".." in p.split("/"):
            errs.append(f"paths: {p!r} must be a relative path of letters, "
                        f"digits, '_', '.', '-' and '/' inside the repo")
        elif not os.path.isdir(os.path.join(root, p)):
            errs.append(f"paths: {p!r} is not a directory")
    paths = [p for p in paths if isinstance(p, str)]
    cmd = m.get("command")
    if not (isinstance(cmd, list) and 1 <= len(cmd) <= 32
            and all(_line(w) for w in cmd)):
        errs.append("command: must be a list of 1 to 32 one-line strings "
                    "of 1 to 200 characters")
    else:
        for w in cmd[1:]:
            if w.startswith("/") or ".." in w.split("/"):
                errs.append(f"command: {w!r} leaves the repo")
            elif ("/" in w or os.path.exists(os.path.join(root, w))) \
                    and not _inside(w, paths):
                errs.append(f"command: {w!r} is not under `paths`")
    rs = m.get("run_seconds")
    if not (isinstance(rs, int) and not isinstance(rs, bool)
            and 1 <= rs <= MAX_RUN_SECONDS):
        errs.append(f"run_seconds: must be a whole number from 1 to "
                    f"{MAX_RUN_SECONDS}, got {rs!r}")
    for p in paths:
        for dirpath, dirnames, filenames in os.walk(os.path.join(root, p)):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            for fn in filenames:
                rel = os.path.relpath(os.path.join(dirpath, fn), root)
                if not FILE_CHARS.match(rel):
                    errs.append(f"paths: file name {rel!r} has a character "
                                f"outside letters, digits, '_', '.', '-'")

    # ---- configurations
    configs = _entries(m, "configs", 1, 24, errs)
    _unique([c.get("name") for c in configs], "configs", errs)
    _unique([c.get("file") for c in configs], "configs file", errs)
    for c in configs:
        w = f"config {c.get('name')!r}"
        _keys(c, CONFIG_KEYS, set(), w, errs)
        _name(c.get("name"), f"{w} name", errs)
        for k in ("source", "why"):
            if not _line(c.get(k)):
                errs.append(f"{w} {k}: must be one line of 1 to 200 "
                            f"characters")
        f = c.get("file")
        if not (isinstance(f, str) and _inside(f, paths)):
            errs.append(f"{w} file: {f!r} must lie under `paths`")
        elif not isinstance(_load(os.path.join(root, f), errs, f"{w} file"),
                            (dict, type(None))):
            errs.append(f"{w} file: {f} must hold a JSON object")
        red = c.get("reduced")
        if not (isinstance(red, list) and len(red) <= 16):
            errs.append(f"{w} reduced: must be a list of at most 16 keys")
            red = []
        for k in red:
            if _name(k, f"{w} reduced key", errs) and WIDTH.search(k):
                errs.append(f"{w} reduced: {k!r} is a width, and a width "
                            f"is never cut")
    config_names = {c.get("name") for c in configs}

    # ---- cells
    cells = _entries(m, "workloads", 1, 24, errs)
    _unique([c.get("name") for c in cells], "workloads", errs)
    _unique([(c.get("config"), c.get("traffic")) for c in cells],
            "workloads (config, traffic)", errs)
    jobs = {}
    for c in cells:
        w = f"workload {c.get('name')!r}"
        _keys(c, CELL_KEYS, set(), w, errs)
        ok = _name(c.get("name"), f"{w} name", errs)
        ok &= _name(c.get("traffic"), f"{w} traffic", errs)
        if c.get("config") not in config_names:
            errs.append(f"{w} config: {c.get('config')!r} is not a "
                        f"configuration")
        if c.get("chips") not in (1, 4) or isinstance(c.get("chips"), bool):
            errs.append(f"{w} chips: must be 1 or 4, got {c.get('chips')!r}")
        if not _line(c.get("why")):
            errs.append(f"{w} why: must be one line of 1 to 200 characters")
        if not ok:
            continue
        wf = os.path.join(HERE, "workloads", c["name"] + ".json")
        if not os.path.isfile(wf):
            errs.append(f"{w}: its workload file benchmark/workloads/"
                        f"{c['name']}.json is missing")
            continue
        wl = _load(wf, errs, w) or {}
        for k in ("config", "traffic", "chips"):
            if wl.get(k) != c.get(k):
                errs.append(f"{w} {k}: the manifest says {c.get(k)!r}, the "
                            f"workload file {wl.get(k)!r}")
        jobs[c["name"]] = wl.get("job")
        for kind, rel in (
                ("traffic", f"traffic/{c['traffic']}.json"),
                ("job", f"jobs/{wl.get('job')}.py"),
                ("config", f"configs/{c.get('config')}.json")):
            if not os.path.isfile(os.path.join(HERE, rel)):
                errs.append(f"{w} {kind}: benchmark/{rel} is missing")
    for name in config_names - {c.get("config") for c in cells}:
        errs.append(f"config {name!r}: no workload uses it")
    cell_names = [c.get("name") for c in cells]
    four = [c.get("name") for c in cells if c.get("chips") == 4]
    if len(four) > max(1, len(cells) // 4):
        errs.append(f"workloads chips: {len(four)} cells ask for four chips "
                    f"({four}); at most {max(1, len(cells) // 4)} may")

    # ---- metrics
    e2e = _entries(m, "end_to_end", 1, 16, errs)
    layer = _entries(m, "per_layer", 1, 128, errs)
    _unique([x.get("name") for x in e2e + layer], "metrics", errs)

    def cells_of(x, w):
        if "workloads" not in x:
            return list(cell_names)
        v = x["workloads"]
        if not (isinstance(v, list) and v
                and all(isinstance(n, str) for n in v)):
            errs.append(f"{w} workloads: must be a non-empty list of "
                        f"workload names")
            return []
        for n in v:
            if n not in cell_names:
                errs.append(f"{w} workloads: {n!r} is not a workload")
        return [n for n in v if n in cell_names]

    def common(x, w, sources):
        _name(x.get("name"), f"{w} name", errs)
        if not (isinstance(x.get("unit"), str) and UNIT.match(x["unit"])):
            errs.append(f"{w} unit: must be 1 to 16 characters from "
                        f"letters, digits, '_', '/', '%', '.' and '-', not "
                        f"{x.get('unit')!r}")
        if x.get("better") not in ("lower", "higher"):
            errs.append(f"{w} better: must be 'lower' or 'higher'")
        if x.get("source") not in sources:
            errs.append(f"{w} source: must be one of {list(sources)}, got "
                        f"{x.get('source')!r}")

    reports = {}                      # end-to-end metric -> its cells
    for x in e2e:
        w = f"end_to_end metric {x.get('name')!r}"
        _keys(x, E2E_KEYS, {"workloads"}, w, errs)
        common(x, w, E2E_SOURCES)
        b = x.get("bound")
        if not (isinstance(b, (int, float)) and not isinstance(b, bool)
                and MIN_BOUND <= b <= MAX_BOUND):
            errs.append(f"{w} bound: must be a number from {MIN_BOUND} to "
                        f"{MAX_BOUND}, got {b!r}")
        reports[x.get("name")] = cells_of(x, w)
    if "setup_s" not in reports:
        errs.append("end_to_end: one metric must be `setup_s`")
    per_cell_layer = {n: 0 for n in cell_names}
    for x in layer:
        w = f"per_layer metric {x.get('name')!r}"
        _keys(x, LAYER_KEYS, {"workloads"}, w, errs)
        common(x, w, SOURCES)
        _name(x.get("layer"), f"{w} layer", errs)
        mine = cells_of(x, w)
        for n in mine:
            per_cell_layer[n] += 1
        mv = x.get("moves")
        if mv not in reports:
            errs.append(f"{w} moves: {mv!r} is not an end_to_end metric")
        else:
            lack = [n for n in mine if n not in reports[mv]]
            if lack:
                errs.append(f"{w} moves: {mv!r} is not reported in {lack}, "
                            f"where this metric is")
        if isinstance(x.get("name"), str) and NAME.match(x["name"]):
            errs.extend(_check_layer_file(x, mine, jobs, w))
    for n in cell_names:
        mine = [k for k, v in reports.items() if n in v]
        if "setup_s" not in mine:
            errs.append(f"workload {n!r}: does not report `setup_s`")
        if len([k for k in mine if k != "setup_s"]) < 1:
            errs.append(f"workload {n!r}: reports no end_to_end metric "
                        f"besides `setup_s`")
        if per_cell_layer.get(n, 0) < 1:
            errs.append(f"workload {n!r}: reports no per_layer metric")
    return errs


def _check_layer_file(x: dict, cells: list, jobs: dict, w: str) -> list:
    """The metric's own file must say what the manifest says, and the
    cells the manifest lists must be the cells its `jobs` select."""
    errs: list = []
    path = os.path.join(HERE, "layer_metrics", x["name"] + ".json")
    if not os.path.isfile(path):
        return [f"{w}: its file benchmark/layer_metrics/{x['name']}.json "
                f"is missing"]
    lm = _load(path, errs, w) or {}
    for k in ("layer", "unit", "better", "source", "moves"):
        if lm.get(k) != x.get(k):
            errs.append(f"{w} {k}: the manifest says {x.get(k)!r}, the "
                        f"metric's file {lm.get(k)!r}")
    want = sorted(n for n, job in jobs.items()
                  if job in lm.get("jobs", ())
                  and n in lm.get("workloads", [n]))
    if want != sorted(cells):
        errs.append(f"{w} workloads: the manifest lists {sorted(cells)}, "
                    f"the metric's file selects {want}")
    mod, _, fn = str(lm.get("reducer", "")).partition(":")
    if not (fn and os.path.isfile(os.path.join(HERE, "reducers",
                                               mod + ".py"))):
        errs.append(f"{w} reducer: {lm.get('reducer')!r} must be "
                    f"'module:function' with benchmark/reducers/<module>.py")
    return errs


def check_file(path: str) -> list:
    errs: list = []
    if os.path.getsize(path) > MAX_BYTES:
        errs.append("manifest: larger than 64 KiB")
    m = _load(path, errs, "manifest")
    return errs + (check(m, os.path.dirname(os.path.abspath(path)))
                   if m is not None else [])


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    path = argv[0] if argv else os.path.join(os.path.dirname(HERE),
                                             "BENCHMARK.json")
    errs = check_file(path)
    for e in errs:
        print(f"check_manifest: {e}")
    print(f"check_manifest: {path}: "
          f"{'refused, ' + str(len(errs)) + ' fault(s)' if errs else 'ok'}")
    return 1 if errs else 0


if __name__ == "__main__":
    sys.exit(main())
