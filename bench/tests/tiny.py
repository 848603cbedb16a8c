"""Sizes a test run holds, for driving whole cells on the CPU, and the
cells the tests drive (``BENCHMARK.json``'s, plus any that the harness
can run but the benchmark does not list yet)."""
import copy

from bench import harness

#: cells the harness's drivers, configurations and mixes define
CELLS = (
    {"name": "convdiff-512-nonblocking", "config": "convdiff-512",
     "traffic": "closed-nonblocking-pfait", "chips": 1},
    {"name": "convdiff-512-blocking", "config": "convdiff-512",
     "traffic": "closed-blocking-sync", "chips": 1},
    {"name": "serve-mix-poisson", "config": "serve-mix",
     "traffic": "poisson-knee80", "chips": 1},
    {"name": "convdiff-1024-2x2-nonblocking", "config": "convdiff-1024-2x2",
     "traffic": "closed-nonblocking-pfait", "chips": 4},
)
_E2E = {"convdiff": ["solve_s"], "serve": ["ttd_p95_s", "ttd_p50_s"]}


def spec():
    """``BENCHMARK.json`` with every cell of ``CELLS`` in it."""
    out = copy.deepcopy(harness.load_spec())
    names = {c["name"] for c in out["workloads"]}
    configs = {c["name"] for c in out["configs"]}
    metrics = {m["name"]: m for m in out["end_to_end"]}
    for cell in CELLS:
        if cell["name"] in names:
            continue
        out["workloads"].append(dict(cell, why="a cell the tests drive"))
        if cell["config"] not in configs:
            configs.add(cell["config"])
            out["configs"].append({
                "name": cell["config"], "source": "", "reduced": [],
                "why": "", "file": f"bench/configs/{cell['config']}.json"})
        for name in _E2E[cell["config"].split("-")[0]]:
            m = metrics.setdefault(name, {"name": name, "unit": "s",
                                          "workloads": []})
            if "workloads" in m:
                m["workloads"].append(cell["name"])
    out["end_to_end"] = list(metrics.values())
    return out


SPEC = spec()

#: solve cells: a 16^3 grid (on the cell's own mesh shape)
SOLVE = {"n": 16}


def service_overrides():
    """The service cell with small tenants, 4 lanes and a short budget."""
    fams = copy.deepcopy(harness.load_config(SPEC, "serve-mix")["families"])
    fams["convdiff"]["problem"]["n"] = 8
    fams["pagerank"]["problem"]["n"] = 64
    fams["mlfixed"]["problem"].update({"n": 16, "m_rows": 48})
    config = {"families": fams,
              "serve": {"lanes": 4, "chunk": 16, "max_staleness": 8,
                        "max_steps": 512}}
    traffic = {"rate": 12.0, "sample": 12, "drain_s": 30.0}
    return config, traffic
