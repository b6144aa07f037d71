"""One cell of ``BENCHMARK.json``, found by name: its configuration's files,
its traffic mix, the data and bindings drawn from the seed, and the plain
reference's answers. Nothing here knows a configuration or a mix by name."""

import dataclasses
import importlib.util
import json
import os
from typing import Dict, List, Optional

import numpy as np

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def by_name(entries: List[Dict], name: str, what: str) -> Dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"BENCHMARK.json has no {what} named {name!r}; it has "
                   f"{[e['name'] for e in entries]}")


@dataclasses.dataclass
class QueryRecord:
    """One query a client sent inside the window; times are
    ``time.perf_counter()`` seconds."""
    client: int
    binding: int
    t_start: float
    t_end: float
    ok: bool                        # it answered; after ``judge``: and rightly
    error: str = ""
    differs: bool = False           # it answered, and not what the reference says
    rows: Optional[List[tuple]] = None   # the answer, kept for ``judge``
    queue_wait_ms: Optional[float] = None
    exec_ms: Optional[float] = None
    traced: bool = False

    @property
    def latency_s(self) -> float:
        return self.t_end - self.t_start


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict
    config_dir: str
    traffic_name: str
    traffic: Dict
    seed: int
    rows: Dict[str, int]            # table -> rows, after any rehearsal scaling
    statement: str                  # template with {placeholders}
    bindings: List[Dict] = dataclasses.field(default_factory=list)
    tables: Dict = dataclasses.field(default_factory=dict)
    paths: Dict[str, str] = dataclasses.field(default_factory=dict)
    answers: List[List[tuple]] = dataclasses.field(default_factory=list)

    @property
    def scan_rows(self) -> int:
        """Input rows of one query: the rows of the tables it scans."""
        return sum(self.rows[t] for t in self.config["scans"])

    def sql(self, binding: int) -> str:
        return self.statement.format(**self.bindings[binding])

    def schedule(self, client: int, clients: int):
        """Endless order of binding indices for one client: a walk round one
        permutation drawn from the seed, every client starting at a place of
        its own, so that every seed sends the same set of statements in
        another order and clients side by side send different ones. The first
        ``warmup_steps`` of all clients together cover every binding."""
        n = len(self.bindings)
        perm = np.random.default_rng([self.seed, 7]).permutation(n)
        at = client * self.warmup_steps(clients)
        while True:
            yield int(perm[at % n])
            at += 1

    def warmup_steps(self, clients: int) -> int:
        return -(-len(self.bindings) // clients)

    # -- set-up ------------------------------------------------------------

    def generate(self) -> None:
        gen = load_module(os.path.join(self.config_dir, "generator.py"),
                          f"{self.config_name}_generator")
        self.tables = gen.generate(self.seed, self.rows)

    def write(self, data_root: str) -> None:
        from benchmarks.harness.tables import write_tables
        self.paths = write_tables(self.tables, self.config["tables"], data_root)

    def compute_answers(self) -> None:
        ref = load_module(os.path.join(self.config_dir, "reference.py"),
                          f"{self.config_name}_reference")
        self.answers = [ref.answer(self.tables, b) for b in self.bindings]
        empty = [b for b, a in zip(self.bindings, self.answers) if not a]
        if empty:
            raise ValueError(f"the reference's answer is empty for {empty}: "
                             "a cell proves nothing on an empty result")


def draw_bindings(seed: int, count: int, config: Dict) -> List[Dict]:
    """``count`` distinct bindings of the statement's placeholders, uniform
    over the configuration's domains; a count of 1 is the default binding."""
    domains = config["binding_domains"]
    if count == 1 or not domains:
        return [dict(config["default_binding"])]
    rng = np.random.default_rng([seed, 11])
    space = 1
    for lo, hi in domains.values():
        space *= hi - lo + 1
    if count > space:
        raise ValueError(f"{count} distinct bindings asked of a domain of {space}")
    out: List[Dict] = []
    while len(out) < count:
        b = {k: int(rng.integers(lo, hi + 1)) for k, (lo, hi) in domains.items()}
        if b not in out:
            out.append(b)
    return out


def make_cell(name: str, chips: int, config_name: str, config_file: str,
              traffic_name: str, seed: int, scale_rows: float = 1.0) -> Cell:
    """A cell from its configuration's file and its traffic mix's name."""
    config = load_json(config_file)
    config_dir = os.path.dirname(config_file)
    traffic = load_json(os.path.join(BENCH_DIR, "traffic", traffic_name + ".json"))
    rows = {t: (max(1000, int(spec["rows"] * scale_rows))
                if spec.get("scales_in_rehearsal") else spec["rows"])
            for t, spec in config["tables"].items()}
    with open(os.path.join(config_dir, config["statement_file"])) as f:
        statement = f.read()
    cell = Cell(name=name, chips=chips, config_name=config_name,
                config=config, config_dir=config_dir, traffic_name=traffic_name,
                traffic=traffic, seed=seed, rows=rows, statement=statement)
    cell.bindings = draw_bindings(seed, int(traffic["bindings"]), config)
    return cell


def load_cell(name: str, seed: int, scale_rows: float = 1.0) -> Cell:
    """The cell of that name in ``BENCHMARK.json``."""
    manifest = load_json(MANIFEST)
    w = by_name(manifest["workloads"], name, "workload")
    c = by_name(manifest["configs"], w["config"], "config")
    return make_cell(name, w["chips"], w["config"],
                     os.path.join(ROOT, c["file"]), w["traffic"], seed,
                     scale_rows)
