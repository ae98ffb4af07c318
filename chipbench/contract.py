"""What ``BENCHMARK.json`` and the files it names have to satisfy, as one
function that runs on any checkout:

    python3 chipbench/contract.py [root]      # prints each violation, exits 1 if any

``violations(root)`` holds the limits the driver refuses a PR for before a
single run (key sets, names, units, bounds, counts, the share of four-chip
cells, a ``workloads`` list on every per-layer metric) and this harness's own
rules (a reader per per-layer metric, a reference, a driver and a model file
per configuration, a configuration's sizes against the ``published`` block of
its own file).  No model's widths are in this code: a configuration is held to
what its own file says its source publishes.  It cannot see one of the
driver's limits: a new cell whose fullest device stays under 25% of the chip's
memory is refused after its first run (README, "How a later PR adds to it").
"""
from __future__ import annotations

import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench.registry import Registry  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
E2E_SOURCES = ("host_clock", "device_trace")
SOURCES = E2E_SOURCES + ("program_span", "program_counter")
# table -> (most entries, keys every entry has, keys an entry may add)
TABLES = {
    "configs": (24, {"name", "source", "file", "reduced", "why"}, set()),
    "workloads": (24, {"name", "config", "traffic", "chips", "why"}, set()),
    "end_to_end": (16, {"name", "unit", "better", "bound", "source"},
                   {"workloads"}),
    "per_layer": (128, {"name", "unit", "better", "source", "layer", "moves",
                        "workloads"}, set()),
}
TOP = {"command", "paths", "run_seconds"} | set(TABLES)
MAX_BYTES = 64 * 1024
MAX_RUN_SECONDS = 51    # (2 + 14 x 24 cells) runs of run_seconds + 60 s, + 24 x 180 s + 1200 s <= 43200 s
MAX_REDUCED = 16
WIDTH_SUFFIXES = ("_dim", "_rank")


def _line(text):
    """1 to 200 characters on one line, with no tab."""
    return isinstance(text, str) and 1 <= len(text) <= 200 and \
        "\n" not in text and "\t" not in text


def _inside(path):
    return not path.startswith("/") and ".." not in path.split("/")


def _shape(bench, size):
    """The keys and the counts: what everything below reads."""
    if set(bench) != TOP:
        return ["BENCHMARK.json: top-level keys %s, not %s"
                % (sorted(bench), sorted(TOP))]
    out = []
    if size >= MAX_BYTES:
        out.append("BENCHMARK.json: %d bytes, over 64 KiB" % size)
    for table, (most, need, may) in TABLES.items():
        if not 1 <= len(bench[table]) <= most:
            out.append("%s: %d entries, not 1 to %d"
                       % (table, len(bench[table]), most))
        for i, e in enumerate(bench[table]):
            where = "%s %s" % (table, e.get("name", "#%d" % i))
            if need - set(e):
                out.append("%s: lacks %s" % (where, sorted(need - set(e))))
            if set(e) - need - may:
                out.append("%s: keys %s are not the contract's"
                           % (where, sorted(set(e) - need - may)))
    return out


def _top(bench):
    out = []
    rs = bench["run_seconds"]
    if not isinstance(rs, int) or not 1 <= rs <= MAX_RUN_SECONDS:
        out.append("run_seconds: %r is not a whole number from 1 to %d"
                   % (rs, MAX_RUN_SECONDS))
    paths, command = bench["paths"], bench["command"]
    if not 1 <= len(paths) <= 16 or not all(
            isinstance(p, str) and PATH.match(p) and _inside(p) for p in paths):
        out.append("paths: %r is not 1 to 16 relative directories" % (paths,))
    if not 1 <= len(command) <= 32 or not all(
            _line(w) and _inside(w) for w in command):
        out.append("command: %r is not 1 to 32 words that stay in the repo"
                   % (command,))
    return out


def _names(bench):
    out = []
    for table in TABLES:
        for e in bench[table]:
            if not isinstance(e["name"], str) or not NAME.match(e["name"]):
                out.append("%s: %r is not a name (at most 64 of A-Z a-z 0-9 _ . -)"
                           % (table, e["name"]))
    for what, tables in (("configs", ["configs"]), ("workloads", ["workloads"]),
                         ("metrics", ["end_to_end", "per_layer"])):
        names = [e["name"] for t in tables for e in bench[t]]
        for n in sorted({n for n in names if names.count(n) > 1}):
            out.append("%s: the name %s appears %d times"
                       % (what, n, names.count(n)))
    return out


def _metrics(reg):
    b = reg.bench
    out = []
    cells = {w["name"] for w in b["workloads"]}
    e2e = {m["name"] for m in b["end_to_end"]}
    if "setup_s" not in e2e:
        out.append("end_to_end: no setup_s")
    for table in ("end_to_end", "per_layer"):
        for m in b[table]:
            where = "%s %s" % (table, m["name"])
            if not isinstance(m["unit"], str) or not UNIT.match(m["unit"]):
                out.append("%s: unit %r" % (where, m["unit"]))
            if m["better"] not in ("lower", "higher"):
                out.append("%s: better is %r" % (where, m["better"]))
            listed = m.get("workloads")
            if listed is not None and (not isinstance(listed, list) or not listed):
                out.append("%s: workloads is %r, not a list of cells: a metric "
                           "with none would apply to every later cell"
                           % (where, listed))
            elif listed is not None and set(listed) - cells:
                out.append("%s: lists %s, no cells of the benchmark"
                           % (where, sorted(set(listed) - cells)))
    for m in b["end_to_end"]:
        where = "end_to_end %s" % m["name"]
        if not isinstance(m["bound"], (int, float)) or \
                not 0.01 <= m["bound"] <= 0.1:
            out.append("%s: bound %r is not within 0.01 to 0.1"
                       % (where, m["bound"]))
        if m["source"] not in E2E_SOURCES:
            out.append("%s: source %r, not one of %s"
                       % (where, m["source"], list(E2E_SOURCES)))
    for m in b["per_layer"]:
        where = "per_layer %s" % m["name"]
        if m["source"] not in SOURCES:
            out.append("%s: source %r, not one of %s"
                       % (where, m["source"], list(SOURCES)))
        if not _line(m["layer"]):
            out.append("%s: layer is not 1 to 200 characters on one line" % where)
        if m["moves"] not in e2e:
            out.append("%s: moves %r, no end-to-end metric" % (where, m["moves"]))
        # which also keeps a serving phase off a training cell, whatever
        # driver file the cell's configuration names
        listed = m["workloads"] if isinstance(m["workloads"], list) else []
        for cell in sorted(cells.intersection(listed)) if m["moves"] in e2e else ():
            if m["moves"] not in {
                    e["name"] for e in reg.metrics("end_to_end", cell)}:
                out.append("%s: lists %s, which does not report %s, the "
                           "metric it moves" % (where, cell, m["moves"]))
        # its reader is a file of its own, found by name
        try:
            reader = reg.module("layer_metrics", m["name"])
        except Exception as e:  # noqa: BLE001 — whatever loading it raises
            out.append("%s: no reader layer_metrics/%s.py loads (%s: %s)"
                       % (where, m["name"], type(e).__name__, e))
        else:
            if not callable(getattr(reader, "read", None)):
                out.append("%s: layer_metrics/%s.py has no read(observed)"
                           % (where, m["name"]))
    return out


def _cells(reg):
    b = reg.bench
    out = []
    cells = b["workloads"]
    configs = {c["name"] for c in b["configs"]}
    four = [w["name"] for w in cells if w["chips"] == 4]
    if len(four) > max(1, len(cells) // 4):
        out.append("workloads: %d of %d cells ask for 4 chips (%s), over a "
                   "quarter rounded down (one always may)"
                   % (len(four), len(cells), ", ".join(four)))
    pairs = [(w["config"], w["traffic"]) for w in cells]
    for pair in sorted({p for p in pairs if pairs.count(p) > 1}):
        out.append("workloads: configuration %s under traffic %s appears %d "
                   "times" % (pair + (pairs.count(pair),)))
    for w in cells:
        where = "workloads %s" % w["name"]
        if w["chips"] not in (1, 4):
            out.append("%s: chips is %r, not 1 or 4" % (where, w["chips"]))
        if not _line(w["why"]):
            out.append("%s: why is not 1 to 200 characters on one line" % where)
        if w["config"] not in configs:
            out.append("%s: config %r is no configuration" % (where, w["config"]))
        if not isinstance(w["traffic"], str) or not NAME.match(w["traffic"]):
            out.append("%s: traffic %r is not a name" % (where, w["traffic"]))
        else:
            try:
                reg.traffic(w["traffic"])
            except (OSError, ValueError) as e:
                out.append("%s: no file traffic/%s.json loads (%s)"
                           % (where, w["traffic"], e))
        own = {m["name"] for m in reg.metrics("end_to_end", w["name"])}
        if "setup_s" not in own or len(own) < 2:
            out.append("%s: reports %s end to end, not setup_s and one more"
                       % (where, sorted(own)))
        if not reg.metrics("per_layer", w["name"]):
            out.append("%s: reports no per-layer metric" % where)
    return out


def _configs(reg):
    b = reg.bench
    out = []
    files = [c["file"] for c in b["configs"]]
    for c in b["configs"]:
        where = "configs %s" % c["name"]
        if not _line(c["why"]) or not _line(c["source"]):
            out.append("%s: why and source are 1 to 200 characters on one line"
                       % where)
        if not any(w["config"] == c["name"] for w in b["workloads"]):
            out.append("%s: no cell uses it" % where)
        reduced = c["reduced"]
        if not isinstance(reduced, list) or len(reduced) > MAX_REDUCED or \
                not all(isinstance(k, str) and NAME.match(k) for k in reduced):
            out.append("%s: reduced %r is not at most %d names"
                       % (where, reduced, MAX_REDUCED))
            continue
        if not isinstance(c["file"], str) or not _inside(c["file"]) or \
                not c["file"].startswith(tuple(p + "/" for p in b["paths"])) \
                or files.count(c["file"]) > 1:
            out.append("%s: file %r is not a file of its own under %s"
                       % (where, c["file"], b["paths"]))
            continue
        try:
            cfg = reg.config(c["name"])
        except (OSError, ValueError) as e:
            out.append("%s: %s does not load (%s)" % (where, c["file"], e))
            continue
        out.extend(_config_file(reg, c, cfg))
    return out


def _config_file(reg, entry, cfg):
    """A configuration against its own file: ``published`` is the source's
    value for every key the source fixes, ``reduced`` the keys that differ
    from it (each with its reason), ``assumed`` what the source does not fix."""
    where = entry["file"]
    out = []
    published = cfg.get("published")
    why = cfg.get("reduced_why")
    assumed = cfg.get("assumed")
    if not isinstance(published, dict) or not published:
        return ["%s: no published block (the source's value for every key "
                "the source fixes)" % where]
    if not isinstance(why, dict) or not isinstance(assumed, dict) or \
            not all(isinstance(v, str) and v for v in
                    list(why.values()) + list(assumed.values())):
        return ["%s: reduced_why and assumed are {key: reason}" % where]
    if cfg.get("reduced") != entry["reduced"]:
        out.append("%s: reduced %r, its BENCHMARK.json entry has %r"
                   % (where, cfg.get("reduced"), entry["reduced"]))
    for k in entry["reduced"]:
        if k.endswith(WIDTH_SUFFIXES):
            out.append("%s: reduced names %s, a width: a width is never cut"
                       % (where, k))
        if k not in cfg or k not in published:
            out.append("%s: reduced names %s, which is not a key of the file "
                       "and of published" % (where, k))
        elif cfg[k] == published[k]:
            out.append("%s: reduced names %s, which is as published (%r)"
                       % (where, k, cfg[k]))
        if k not in why:
            out.append("%s: reduced names %s, reduced_why has no line for it"
                       % (where, k))
    for k in sorted(set(why) - set(entry["reduced"])):
        out.append("%s: reduced_why has %s, which reduced does not name"
                   % (where, k))
    for k in sorted(set(published) - set(entry["reduced"])):
        if k not in cfg or cfg[k] != published[k]:
            out.append("%s: %s is %r, published %r, and is not in reduced"
                       % (where, k, cfg.get(k), published[k]))
    for k in sorted(set(assumed) & set(published)):
        out.append("%s: %s is both assumed and published" % (where, k))
    for kind, key in (("drivers", "driver"), ("models", "model")):
        name = cfg.get(key)
        if not isinstance(name, str) or not os.path.isfile(
                os.path.join(reg.home, kind, name + ".py")):
            out.append("%s: %s %r names no file %s/<%s>.py"
                       % (where, key, name, kind, key))
    try:
        reg.reference(entry["name"])
    except Exception as e:  # noqa: BLE001 — whatever loading it raises
        out.append("%s: no plain reference %s.reference.py loads beside it "
                   "(%s: %s)" % (where, os.path.splitext(where)[0],
                                 type(e).__name__, e))
    return out


def violations(root=ROOT):
    """Every rule that ``root``'s BENCHMARK.json and the files it names
    break, one line each; ``[]`` where there is none."""
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    out = _shape(bench, os.path.getsize(path))
    if out:         # the rules below read these keys
        return out
    reg = Registry(root)
    return (_top(bench) + _names(bench) + _metrics(reg) + _cells(reg)
            + _configs(reg))


def main(argv=None):
    args = sys.argv[1:] if argv is None else argv
    found = violations(args[0] if args else ROOT)
    for v in found:
        print(v)
    print("%d violation%s" % (len(found), "" if len(found) == 1 else "s"))
    return 1 if found else 0


if __name__ == "__main__":
    sys.exit(main())
