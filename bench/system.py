"""The system under test, driven through its serving entry point.

Every cell calls ``repro.serve.coloring.ColoringService.submit``, which
runs ``ColoringPlan.run`` (``core/plan.py``): request inputs, transfers,
the compiled loop program, and the gather of the colors to the host.
The benchmark hands the program the graph it made (``bench.graphs``);
the program builds its own CSR and partition from it.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np


@dataclasses.dataclass
class Answer:
    colors: np.ndarray          # (n,) the coloring the request returned
    rounds: int = 0             # conflict rounds across parts
    comm_bytes: int = 0         # measured exchange payload


class System:
    """One configuration's service; ``self(request) -> Answer``."""

    def __init__(self, config: dict, g):
        from repro.graph.csr import build_graph
        from repro.graph.partition import partition_graph
        from repro.serve.coloring import ColoringService

        t0 = time.perf_counter()
        graph = build_graph(g.src, g.dst, g.n, name=config["name"])
        pg = partition_graph(graph, int(config["parts"]),
                             second_layer=config["problem"] != "d1")
        self.graph_build_s = time.perf_counter() - t0
        self.service = ColoringService(
            pg, problem=config["problem"], backend=config["backend"],
            exchange=config["exchange"], engine=config["engine"])
        self.plan = self.service.plan

    @property
    def plan_build_s(self) -> float:
        return self.plan.stats.build_ms / 1e3

    @property
    def compile_s(self) -> float:
        return self.plan.stats.compile_ms / 1e3

    def __call__(self, req) -> Answer:
        res = self.service.submit(color_mask=req.mask, colors0=req.colors0)
        return Answer(res.colors, int(res.rounds), int(res.comm_bytes_total))
