"""A configuration's deployment, made from its file and the seed.

A configuration file names its members (op scripts under
``perfbench/ops``, with any parameters of theirs), the rank count, the problem class, the cluster's
power tables and the policies.  From a seed this module records each
member's op script once and turns it into two inputs that share
nothing but that script:

* the program's: a ``JobDependencyGraph`` built through the program's
  public ``TraceBuilder``, and ``NodeSpec``\\ s made from the file's
  power tables;
* the reference's: plain job and rank tuples (:mod:`pb.reference`),
  built here by the same dependency convention.
"""

from __future__ import annotations

import hashlib
import importlib.util
import random
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

from . import reference as ref

BENCH_DIR = Path(__file__).resolve().parents[1]
OPS_DIR = BENCH_DIR / "ops"


class OpScript:
    """Records the calls of an op script, in order (the ``TraceBuilder``
    call vocabulary: compute, collective, send, recv)."""

    def __init__(self, n_ranks: int):
        self.n = n_ranks
        self.calls: List[tuple] = []

    def compute(self, node: int, work: float, cpu_frac: float = 1.0):
        self.calls.append(("compute", node, float(work), float(cpu_frac)))

    def collective(self, name: str, group: Sequence[int]):
        self.calls.append(("coll", name, tuple(group)))

    def send(self, src: int, dst: int):
        self.calls.append(("send", src, dst))

    def recv(self, dst: int, src: int):
        self.calls.append(("recv", dst, src))

    def zeroed(self) -> "OpScript":
        """The same script with no work: same shapes, a few waves."""
        out = OpScript(self.n)
        out.calls = [("compute", c[1], 0.0, c[3]) if c[0] == "compute"
                     else c for c in self.calls]
        return out

    def replay(self, tb):
        """Replay the calls into a builder with the same vocabulary."""
        for c in self.calls:
            if c[0] == "compute":
                tb.compute(c[1], c[2], cpu_frac=c[3])
            elif c[0] == "coll":
                tb.collective(c[1], list(c[2]))
            elif c[0] == "send":
                tb.send(c[1], c[2])
            else:
                tb.recv(c[1], c[2])
        return tb


def load_module(path: Path, name: str):
    """Import one file of the benchmark by its path."""
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def record(script_name: str, n_ranks: int, scale: float, seed: int,
           **params) -> OpScript:
    """Run op script ``ops/<script_name>.py`` with its own rng and the
    member's ``params`` (such as ``iterations``)."""
    mod = load_module(OPS_DIR / f"{script_name}.py", f"ops_{script_name}")
    tb = OpScript(n_ranks)
    mod.script(tb, n_ranks, scale, random.Random(seed), **params)
    return tb


def reference_jobs(script: OpScript) -> Tuple[Tuple[ref.Job, ...], ...]:
    """Per-rank job lists with their dependencies.

    The convention: a compute call appends a job to its rank; an op
    ends the rank's last job (a zero-work job is put in first when the
    rank's last job already ends in an op, or it has none); a rank whose
    last job ends in an op gets a zero-work job after it.  An op's job
    is its *producer*, the rank's next job its *child*.  Collectives
    match by occurrence order per (name, group): each member's child
    depends on every other member's producer.  Sends and receives pair
    in order per (src, dst): the receiver's child depends on the
    sender's producer.  Every job also depends on its rank's previous
    job.
    """
    segs: List[List[list]] = [[] for _ in range(script.n)]

    def end_with(node, op):
        if not segs[node] or segs[node][-1][2] is not None:
            segs[node].append([0.0, 1.0, None])
        segs[node][-1][2] = op

    for c in script.calls:
        if c[0] == "compute":
            segs[c[1]].append([c[2], c[3], None])
        elif c[0] == "coll":
            group = tuple(sorted(c[2]))
            for node in c[2]:
                end_with(node, ("coll", c[1], group))
        elif c[0] == "send":
            end_with(c[1], ("send", c[2]))
        else:
            end_with(c[1], ("recv", c[2]))
    for s in segs:
        if s and s[-1][2] is not None:
            s.append([0.0, 1.0, None])

    deps: Dict[Tuple[int, int], List[Tuple[int, int]]] = {}
    colls: Dict[tuple, List[List[tuple]]] = {}
    sends: Dict[tuple, list] = {}
    recvs: Dict[tuple, list] = {}
    for node, s in enumerate(segs):
        seen: Dict[tuple, int] = {}
        for k, (_, _, op) in enumerate(s):
            if op is None:
                continue
            if op[0] == "coll":
                key = (op[1], op[2])
                idx = seen.get(key, 0)
                seen[key] = idx + 1
                occ = colls.setdefault(key, [])
                while len(occ) <= idx:
                    occ.append([])
                occ[idx].append((node, k))
            elif op[0] == "send":
                sends.setdefault((node, op[1]), []).append((node, k))
            else:
                recvs.setdefault((op[1], node), []).append((node, k + 1))
    for (name, group), occs in colls.items():
        for members in occs:
            if {m[0] for m in members} != set(group):
                raise ValueError(f"collective {name} mismatched")
            for node, k in members:
                child = deps.setdefault((node, k + 1), [])
                child.extend(m for m in members if m[0] != node)
    for channel in set(sends) | set(recvs):
        p, c = sends.get(channel, []), recvs.get(channel, [])
        if len(p) != len(c):
            raise ValueError(f"unmatched send/recv on {channel}")
        for prod, child in zip(p, c):
            deps.setdefault(child, []).append(prod)
    return tuple(
        tuple(ref.Job(w, rho, tuple(([(node, k - 1)] if k else [])
                                    + deps.get((node, k), [])))
              for k, (w, rho, _) in enumerate(s))
        for node, s in enumerate(segs))


def draw_ranks(cfg: dict, n_ranks: int, seed: int) -> Tuple[ref.Rank, ...]:
    """The cluster: ranks cycle through ``cluster.pattern`` (power table,
    base speed), each speed jittered by ``speed_jitter`` from ``seed``."""
    rng = random.Random(seed)
    pattern = cfg["cluster"]["pattern"]
    jitter = float(cfg["cluster"].get("speed_jitter", 0.0))
    out = []
    for i in range(n_ranks):
        lut, speed = pattern[i % len(pattern)]
        if jitter:
            speed = speed * rng.uniform(1.0 - jitter, 1.0 + jitter)
        table = cfg["luts"][lut]
        out.append(ref.Rank(tuple((float(f), float(p))
                                  for f, p in table["states"]),
                            float(table["idle_w"]), float(speed)))
    return tuple(out)


def program_specs(cfg: dict, ranks: Sequence[ref.Rank]):
    """The program's ``NodeSpec``\\ s for ``ranks``."""
    from repro.core.power import NodeSpec, PowerLUT, PowerState

    by_states = {tuple(map(tuple, t["states"])): name
                 for name, t in cfg["luts"].items()}
    luts = {}
    specs = []
    for r in ranks:
        lut = luts.get(r.states)
        if lut is None:
            lut = luts[r.states] = PowerLUT(
                name=by_states[r.states],
                states=tuple(PowerState(f, p) for f, p in r.states),
                idle_w=r.idle_w)
        specs.append(NodeSpec(lut, speed=r.speed))
    return tuple(specs)


@dataclass
class Member:
    """One member of a deployment, in both of its forms."""

    name: str
    script: OpScript
    ranks: Tuple[ref.Rank, ...]
    graph: object                 # the program's JobDependencyGraph
    specs: tuple                  # the program's NodeSpecs
    ref_jobs: tuple
    bound_lo: float               # least feasible cluster bound (W)
    bound_hi: float               # bound above which nothing gains (W)

    def bound(self, frac: float) -> float:
        """The cluster bound at ``frac`` of the useful range."""
        return self.bound_lo + frac * (self.bound_hi - self.bound_lo)

    def warm_graph(self):
        """The program's graph of the zero-work script: same shapes."""
        from repro.core.workloads import TraceBuilder

        return self.script.zeroed().replay(TraceBuilder(self.script.n)).build()


def build_member(cfg: dict, entry: dict, graph_seed: int,
                 cluster_seed: int) -> Member:
    """One member from its op script and cluster seeds."""
    from repro.core.workloads import TraceBuilder

    n = int(cfg["ranks"])
    params = {k: v for k, v in entry.items() if k not in ("name", "script")}
    script = record(entry["script"], n, float(cfg["class_scale"]),
                    graph_seed, **params)
    ranks = draw_ranks(cfg, n, cluster_seed)
    graph = script.replay(TraceBuilder(n)).build()
    return Member(
        name=entry["name"], script=script, ranks=ranks, graph=graph,
        specs=program_specs(cfg, ranks), ref_jobs=reference_jobs(script),
        bound_lo=sum(r.states[0][1] for r in ranks),
        bound_hi=max(r.states[-1][1] for r in ranks) * n)


def build_deployment(cfg: dict, seed: int) -> List[Member]:
    """Every member of ``cfg``, drawn from ``seed``: per member, one
    seed for its op script and one for its cluster's speeds."""
    rng = random.Random(f"deploy/{seed}")
    return [build_member(cfg, e, rng.randrange(1 << 16),
                         rng.randrange(1 << 16)) for e in cfg["members"]]


def digest(parts: Sequence[str]) -> str:
    """Short content digest of what a run generated."""
    h = hashlib.sha256()
    for p in parts:
        h.update(p.encode())
        h.update(b"\0")
    return h.hexdigest()[:16]


def member_digest(m: Member) -> str:
    return digest([m.graph.to_text(), repr(m.ranks)])


def ref_scenario(m: Member, bound_w: float, policy: str) -> ref.RefScenario:
    return ref.RefScenario(m.ref_jobs, m.ranks, float(bound_w), policy)
