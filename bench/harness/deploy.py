"""A configuration's problems, built twice from the same data: as the
program's ``LayerDAG``/``Environment`` for the system under test, and as
``reference.Problem`` for the plain reference."""
from __future__ import annotations

from typing import List

import numpy as np

from . import reference as ref
from .spec import load_net


class Deployment:
    """The fleet of (DAG, deadline) problems a configuration lists, over
    its environment."""

    def __init__(self, config: dict):
        self.config = config
        self.env = ref.environment_from_config(config["environment"])
        cols = config["problems_columns"]
        rows = [dict(zip(cols, r)) for r in config["problems"]]
        if len(rows) != int(config["n_problems"]):
            raise ValueError(f"config {config['name']!r} lists {len(rows)} "
                             f"problems, n_problems says "
                             f"{config['n_problems']}")
        nets = {r["net"]: load_net(r["net"]) for r in rows}
        self.rows = rows
        self._dags = []
        for r in rows:
            net = nets[r["net"]]
            p = len(net["compute_cpu_s"])
            pinned = np.full(p, -1, np.int64)
            pinned[int(net["pinned_layer"])] = int(r["pin_server"])
            self._dags.append(dict(
                compute=np.asarray(net["compute_cpu_s"], float),
                edges=np.asarray(net["edges"], np.int64).reshape(-1, 2),
                edge_mb=np.asarray(net["edge_mb"], float),
                app_id=np.zeros(p, np.int64),
                deadline=np.asarray([float(r["deadline_s"])]),
                pinned=pinned))

    def __len__(self) -> int:
        return len(self._dags)

    def ref_problem(self, i: int, env: ref.Environment = None
                    ) -> ref.Problem:
        return ref.Problem(env=env if env is not None else self.env,
                           **self._dags[i])

    def layer_counts(self) -> List[int]:
        return [int(d["compute"].shape[0]) for d in self._dags]

    def edge_counts(self) -> List[int]:
        return [int(d["edges"].shape[0]) for d in self._dags]

    def program_env(self, core, env: ref.Environment = None):
        e = env if env is not None else self.env
        return core.Environment(power=e.power.copy(),
                                cost_per_sec=e.cost_per_sec.copy(),
                                tier=e.tier.copy(),
                                bandwidth=e.bandwidth.copy(),
                                tran_cost=e.tran_cost.copy())

    def program_dags(self, core) -> list:
        return [core.LayerDAG(compute=d["compute"], edges=d["edges"],
                              edge_mb=d["edge_mb"], app_id=d["app_id"],
                              deadline=d["deadline"], pinned=d["pinned"])
                for d in self._dags]

    def solver_config(self, core):
        c = self.config
        return core.PSOGAConfig(
            pop_size=int(c["pop_size"]), max_iters=int(c["max_iters"]),
            stall_iters=int(c["stall_iters"]),
            faithful_sim=bool(c["faithful_sim"]),
            fitness_backend=str(c["fitness_backend"]))
