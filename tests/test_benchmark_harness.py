"""The benchmark's own tests (`benchmarks/tests`) under the tier-1
command, which collects `tests/` only: every test function and fixture of
every `benchmarks/tests/test_*.py` is collected here under its own name, so
each counts and each failure names its test. (By hand they still run as
`JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q`.)
"""
import glob
import importlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _is_fixture(obj):
    return hasattr(obj, "_pytestfixturefunction") \
        or type(obj).__name__ == "FixtureFunctionDefinition"


COLLECTED = {}
for _path in sorted(glob.glob(os.path.join(ROOT, "benchmarks", "tests",
                                           "test_*.py"))):
    _name = os.path.splitext(os.path.basename(_path))[0]
    _module = importlib.import_module("benchmarks.tests." + _name)
    for _attr, _obj in vars(_module).items():
        if (_attr.startswith("test") and callable(_obj)) \
                or _is_fixture(_obj):
            # two files' tests of one name would shadow each other here
            assert _attr not in COLLECTED, (_attr, _name, COLLECTED[_attr])
            COLLECTED[_attr] = _name
            globals()[_attr] = _obj


def test_the_benchmarks_tests_are_collected_here():
    files = {os.path.splitext(os.path.basename(p))[0] for p in glob.glob(
        os.path.join(ROOT, "benchmarks", "tests", "test_*.py"))}
    assert files and set(COLLECTED.values()) == files
    assert sum(1 for n in COLLECTED if n.startswith("test")) >= 48
