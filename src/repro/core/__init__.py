"""The paper's contribution: power redistribution under a cluster bound.

Layers:
  graph          — job dependency graph, max-depths, depth ranges (§III/§IV-A)
  power          — DVFS LUTs, tau(J, P), Eq. (3) multicore power gain (§V-A)
  ilp            — paper ILP + beyond-paper exact-makespan MILP (§IV-B)
  block_detector — report messages + ski-rental debounce (§V-A, §VII-A2)
  heuristic      — Algorithm 1 online controller (§V-B)
  simulator      — policy-agnostic discrete-event cluster simulator (§VI);
                   policies live in repro.policies (string-keyed registry)
  batchsim       — vectorized batch simulator: B scenarios x N nodes as
                   arrays (SweepEngine's executor="vector" backend)
  sweep          — batched (graph, bound, policy) scenario engine with
                   padded mixed-shape bucketing
  scenarios      — seeded ScenarioFamily generators (mixed shapes,
                   relative bounds, dynamic bound steps)
  workloads      — Listing-2 example, NPB analogues (IS, EP, CG, LU),
                   random layered / fork-join generators, pipeline/MoE
                   graphs
  hlo_extract    — job graphs from compiled JAX/XLA steps (§VII-A1 analogue)
  roofline       — three-term roofline from dry-run artifacts
"""

from .batchsim import (BatchArrays, BatchSimulator, GraphArrays,
                       build_graph_arrays, simulate_batch,
                       stack_graph_arrays)
from .block_detector import (DistributeMessage, NodeState, ReportManager,
                             ReportMessage, blocked_report, running_report)
from .graph import Job, JobDependencyGraph, JobId
from .heuristic import PowerDistributionController
from .ilp import (PowerAssignment, assignment_peak_power,
                  build_makespan_milp, equal_share_assignment,
                  solve_paper_ilp)
from .power import (NodeSpec, PowerLUT, PowerState, arndale_like_lut,
                    heterogeneous_cluster, homogeneous_cluster, job_time,
                    max_useful_cluster_bound, min_feasible_cluster_bound,
                    nominal_bound, odroid_like_lut, progress_rate,
                    tpu_v5e_lut)
from .scenarios import (FamilyMember, ScenarioFamily, lm_family,
                        mixed_family, npb_family, random_layered_family)
from .simulator import SimResult, Simulator, simulate
from .sweep import (MapRecord, Scenario, SweepEngine, SweepRecord,
                    SweepResult, compare_policies, scenario_grid)
from .workloads import (LISTING2_TIMES, MatchReport, TraceBuilder,
                        cg_builder, cg_like, ep_builder, ep_like,
                        fork_join_graph, is_builder, is_like, layered_dag,
                        listing2_graph, listing2_random, listing2_uniform,
                        lu_builder, lu_like, match_comm_ops,
                        moe_step_builder, moe_step_graph, pipeline_graph)

__all__ = [k for k in dir() if not k.startswith("_")]
