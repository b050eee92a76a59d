"""Find a cell's parts by the names ``BENCHMARK.json`` gives them.

A later PR adds a configuration, a traffic mix, a driver or a per-layer
reader by adding files and ``BENCHMARK.json`` entries; nothing here lists
a name, so no file that is there needs an edit.
"""
import importlib.util
import json
import pathlib


class Catalog:
    def __init__(self, root):
        """``root`` is the checkout: it holds ``BENCHMARK.json`` and the
        benchmark's directory ``benchmark/chip``."""
        self.root = pathlib.Path(root)
        self.dir = self.root / "benchmark" / "chip"
        with open(self.root / "BENCHMARK.json") as f:
            self.spec = json.load(f)
        self._modules = {}

    # -- BENCHMARK.json ------------------------------------------------------
    def _entry(self, group, name):
        for e in self.spec[group]:
            if e["name"] == name:
                return e
        raise SystemExit("chip benchmark: BENCHMARK.json has no %s named %r "
                         "(it has: %s)" % (group, name, ", ".join(
                             e["name"] for e in self.spec[group])))

    def cell(self, name):
        return self._entry("workloads", name)

    def metrics(self, group, cell_name):
        """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
        return [m for m in self.spec[group]
                if cell_name in m.get("workloads", [cell_name])]

    # -- files found by name -------------------------------------------------
    def _module(self, path):
        path = pathlib.Path(path)
        if path not in self._modules:
            if not path.is_file():
                raise SystemExit("chip benchmark: no file %s" % path)
            spec = importlib.util.spec_from_file_location(
                "chipbench_found_" + "_".join(path.with_suffix("").parts[-2:]),
                path)
            module = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(module)
            self._modules[path] = module
        return self._modules[path]

    @staticmethod
    def _sizes(path, rehearse):
        with open(path) as f:
            sizes = json.load(f)
        toy = sizes.pop("rehearsal", {})
        if rehearse:
            sizes.update(toy)
        return sizes

    def config(self, name, rehearse=False):
        """(sizes, module) of a configuration: the JSON that BENCHMARK.json
        names as its ``file`` and the Python module that JSON names beside
        it (builder, plain reference, FLOP function)."""
        path = self.root / self._entry("configs", name)["file"]
        sizes = self._sizes(path, rehearse)
        return sizes, self._module(path.parent / sizes["module"])

    def traffic(self, name, rehearse=False):
        return self._sizes(self.dir / "traffic" / (name + ".json"), rehearse)

    def driver(self, name):
        return self._module(self.dir / "drivers" / (name + ".py"))

    def readers(self):
        """Every per-layer metric reader, in file-name order."""
        return [self._module(p)
                for p in sorted((self.dir / "layer_metrics").glob("*.py"))]
