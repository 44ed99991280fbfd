"""Find everything a cell needs by the names in ``BENCHMARK.json``.

Nothing here knows a cell, a configuration, a traffic mix or a metric by
name: a later PR adds a ``workloads`` entry and files of its own and edits
no file that is there.  ``roots`` is searched in order, so a test can put a
tiny configuration in front of the real directory.

    configs/<config>.json     sizes, dtype, key axes, chips, guarantees
    traffic/<traffic>.json    driver, operand, request kinds, limits, why
    drivers/<driver>.py       ``run(cell, ...)``: the load loop
    steps/<call>.py           one call of a request: the program's side,
                              the reference's, the roofline's
    fns/<fn>.py               a map body, spelled for both sides
    fetches/<fetch>.py        how the caller takes the answer
    operands/<name>.py        what the steps are applied to
    metrics/<metric>.json     reader, its arguments
    readers/<reader>.py       ``read(ctx, **args)`` -> number or None
    peaks.json                published peaks keyed by ``device_kind``
"""

import importlib.util
import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
HERE = os.path.dirname(os.path.abspath(__file__))
REAL = os.path.join(os.path.dirname(HERE), "BENCHMARK.json")


class ManifestError(ValueError):
    """The manifest, or a file it names, is missing or malformed."""


def _load_json(path):
    with open(path) as fh:
        return json.load(fh)


class Manifest:
    def __init__(self, path, roots=(HERE,)):
        self.path = path
        self.roots = tuple(roots)
        self.doc = _load_json(path)
        self.metrics = {}
        self._modules = {}
        for group in ("end_to_end", "per_layer"):
            for m in self.doc[group]:
                check_name(m["name"], "metric")
                if not UNIT.match(m["unit"]):
                    raise ManifestError("metric %s: unit %r is not made of "
                                        "letters, digits and _/%%.-"
                                        % (m["name"], m["unit"]))
                if m["source"] not in SOURCES:
                    raise ManifestError("metric %s: unknown source %r"
                                        % (m["name"], m["source"]))
                if m["better"] not in ("lower", "higher"):
                    raise ManifestError("metric %s: better is %r"
                                        % (m["name"], m["better"]))
                if m["name"] in self.metrics:
                    raise ManifestError("metric %s named twice" % m["name"])
                self.metrics[m["name"]] = dict(m, group=group)
        self.cells = {}
        for w in self.doc["workloads"]:
            for key in ("name", "config", "traffic"):
                check_name(w[key], key)
            self.cells[w["name"]] = w

    def cell(self, name):
        if name not in self.cells:
            raise ManifestError("unknown workload %r (known: %s)"
                                % (name, ", ".join(sorted(self.cells))))
        return self.cells[name]

    def find(self, kind, name, ext):
        check_name(name, kind)
        for root in self.roots:
            path = os.path.join(root, kind, name + ext)
            if os.path.isfile(path):
                return path
        raise ManifestError("no %s/%s%s under %s"
                            % (kind, name, ext, ", ".join(self.roots)))

    def config(self, name):
        known = {c["name"] for c in self.doc["configs"]}
        if name not in known:
            raise ManifestError("unknown configuration %r" % (name,))
        return _load_json(self.find("configs", name, ".json"))

    def traffic(self, name):
        return _load_json(self.find("traffic", name, ".json"))

    def module(self, kind, name):
        path = self.find(kind, name, ".py")
        if path not in self._modules:
            spec = importlib.util.spec_from_file_location(
                "benchmark_%s_%s" % (kind, re.sub(r"\W", "_", name)), path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            self._modules[path] = mod
        return self._modules[path]

    def cell_metrics(self, cell, group):
        """The manifest's metrics of ``group`` that ``cell`` reports: those
        that list it under ``workloads``, and those with no such key whose
        end-to-end metric (``moves``, or the metric itself) the cell
        reports."""
        e2e = {m["name"] for m in self.doc["end_to_end"]
               if "workloads" not in m or cell in m["workloads"]}
        out = []
        for m in self.doc[group]:
            if "workloads" in m:
                if cell in m["workloads"]:
                    out.append(m)
            elif group == "end_to_end" or m["moves"] in e2e:
                out.append(m)
        return out

    def metric_spec(self, name):
        if name not in self.metrics:
            raise ManifestError("unknown metric %r" % (name,))
        spec = _load_json(self.find("metrics", name, ".json"))
        if "reader" not in spec:
            raise ManifestError("metrics/%s.json names no reader" % name)
        return spec


def check_name(name, what):
    if not isinstance(name, str) or not NAME.match(name):
        raise ManifestError("%s name %r is not made of at most 64 letters, "
                            "digits, _, . and -" % (what, name))


def peaks(device_kind, roots=(HERE,)):
    """The published peaks of ``device_kind``.  A device that is not in the
    table is an error, never a default."""
    for root in roots:
        path = os.path.join(root, "peaks.json")
        if os.path.isfile(path):
            table = _load_json(path)
            if device_kind not in table:
                raise ManifestError(
                    "device_kind %r is not in %s (known: %s): add its "
                    "published peaks with their source before measuring "
                    "on it" % (device_kind, path, ", ".join(sorted(table))))
            return table[device_kind]
    raise ManifestError("no peaks.json under %s" % ", ".join(roots))
