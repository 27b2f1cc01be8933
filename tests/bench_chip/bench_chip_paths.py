"""Where the chip benchmark lives, for its tests.  Its modules import
each other by their plain names (``run.py`` puts its directory first on
``sys.path``); ``load`` does the same and imports one by its path."""
import importlib.util
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CHIP = os.path.join(REPO, "benchmarks", "chip")
BENCHMARK = os.path.join(REPO, "BENCHMARK.json")


def load(name: str):
    """The benchmark module ``name`` (its siblings importable)."""
    if CHIP not in sys.path:
        sys.path.insert(0, CHIP)
    if name in sys.modules and getattr(sys.modules[name], "__file__",
                                       "").startswith(CHIP):
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(CHIP, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod
