"""Tiny versions of the benchmark's configurations and cells for CPU tests:
the same shapes and templates, few tables, a small vocabulary so that
values repeat across tables.  Importing it puts the root of the checkout on
``sys.path``, so the tests import the benchmark as the ``bench`` package.

Besides the benchmark's cells, ``all_seekers`` puts the gittables shapes
under ``all_seekers.json``, a traffic of every seeker kind and combiner
(the program loadgen's six templates), so that the reference is held to
the served path on all four seekers."""
import copy
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import harness, lakegen  # noqa: E402
from bench.traffic import loadgen  # noqa: E402

CELLS = ("webtables_uniform.mc", "gittables_uniform.union", "all_seekers")


def full_cell(name: str) -> dict:
    """A cell at its own size, as a run loads it."""
    if name != "all_seekers":
        return harness.load_cell(name)
    c = harness.load_cell("webtables_uniform.mc")
    c["name"] = name
    c["config"] = lakegen.load_config("gittables_uniform")
    c["traffic"] = loadgen.load_traffic(Path(__file__).parent /
                                        "all_seekers.json")
    return c


def config(cfg: dict, n_tables: int = 60, vocab: int = 300,
           rows_clip: int = 32) -> dict:
    cfg = copy.deepcopy(cfg)
    cfg["n_tables"] = n_tables
    cfg["tables"]["rows_log_uniform"] = [4, 40]
    cfg["tables"]["rows_clip"] = rows_clip
    cfg["values"]["vocab"] = vocab
    return cfg


def cell(name: str, **kw) -> dict:
    c = full_cell(name)
    c["config"] = config(c["config"], **kw)
    # CPU tests compile inside the window; every answer counts as in time
    c["params"]["limit_ms"] = 1e6
    if c["traffic"]["loop"] == "open":
        c["traffic"]["pool"]["n_distinct"] = 48
        c["params"]["rate_rps"] = 30.0
    else:
        c["params"]["clients"] = 4
    return c
