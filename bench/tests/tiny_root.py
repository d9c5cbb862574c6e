"""A checkout-shaped directory holding tiny copies of the benchmark's
cells, for tests that drive the whole benchmark on the CPU.

Each copy keeps its cell's configuration, mix and limits and changes
only their scale: fewer keys per model (the design rates scaled with
them), a lower offered rate, a 1 s burn-in and quanta of at most 1024
requests.  Besides the cells of ``BENCHMARK.json`` the tests drive two
more:

* ``spill_fleet.steady``, whose configuration and mix are ready but
  whose cell waits for its knee sweep on the chip (PERF.md, Open
  questions): it keeps the multi-leg replay held to the program.  It has
  no limits of its own yet and is held here to the platform cell's;
* ``platform_131k.closed``, the platform fleet under a closed loop of
  clients, which drives the loop's closed arrival process.
"""
from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
#: cell -> (keys per model, offered requests/s)
TINY = {"platform_131k.steady": (2048, 300.0),
        "spill_fleet.steady": (256, 200.0),
        "platform_131k.closed": (2048, 300.0)}
SPILL = {"config": {"name": "spill_fleet",
                    "file": "bench/configs/spill_fleet.json"},
         "cell": {"name": "spill_fleet.steady", "config": "spill_fleet",
                  "traffic": "spill_steady", "chips": 1}}
CLOSED = {"cell": {"name": "platform_131k.closed", "config": "platform_131k",
                   "traffic": "platform_closed", "chips": 1},
          "from": "platform_steady",
          "arrivals": {"process": "closed", "clients": 64, "think_s": 0.5}}
LIMITS_OF = {"spill_fleet.steady": "platform_131k.steady",
             "platform_131k.closed": "platform_131k.steady"}


def make(tmp: Path) -> Path:
    root = Path(tmp)
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    (root / "src").symlink_to(REPO / "src")
    doc = json.loads((REPO / "BENCHMARK.json").read_text())
    names = {w["name"] for w in doc["workloads"]}
    if SPILL["cell"]["name"] not in names:
        doc["configs"].append(SPILL["config"])
        doc["workloads"].append(SPILL["cell"])
    doc["workloads"].append(CLOSED["cell"])
    traffic = root / "bench" / "traffic"
    mix = json.loads((traffic / f"{CLOSED['from']}.json").read_text())
    mix["arrivals"] = dict(CLOSED["arrivals"])
    (traffic / f"{CLOSED['cell']['traffic']}.json").write_text(
        json.dumps(mix))
    limits = root / "bench" / "limits"
    for cell, like in LIMITS_OF.items():
        if not (limits / f"{cell}.json").exists():
            shutil.copy(limits / f"{like}.json", limits / f"{cell}.json")
    files = {c["name"]: c["file"] for c in doc["configs"]}
    for cell in doc["workloads"]:
        keys, rate = TINY[cell["name"]]
        path = root / files[cell["config"]]
        cfg = json.loads(path.read_text())
        f = keys / cfg["keys_per_model"]
        cfg["keys_per_model"] = keys
        for k in ("design_tokens_per_s", "design_concurrency"):
            if k in cfg:
                cfg[k] *= f
        path.write_text(json.dumps(cfg))
        mix_path = root / "bench" / "traffic" / f"{cell['traffic']}.json"
        mix = json.loads(mix_path.read_text())
        mix.update(burn_in_s=1.0, quantum_cap=1024)
        arrivals = mix["arrivals"]
        if arrivals["process"] == "closed":
            arrivals["max_rps"] = rate
        else:
            arrivals["rate_rps"] = rate
        mix_path.write_text(json.dumps(mix))
    (root / "BENCHMARK.json").write_text(json.dumps(doc))
    return root
