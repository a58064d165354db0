"""Published peaks of the chips the benchmark runs on, and the work the
two replay kernels need, counted from shapes.

A kernel's roofline share is the least time the chip could take for the
work, the larger of operations over peak operations per second and
bytes over peak memory bandwidth, divided by the kernel's device time.
"""
from __future__ import annotations

from typing import Dict, NamedTuple

#: keyed by ``jax.Device.device_kind``
PEAKS: Dict[str, dict] = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,          # bf16 matrix units
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s",
    },
}

F32 = 4


class Work(NamedTuple):
    flops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def seconds(self, device_kind: str) -> float:
        """The least time the chip needs: compute- or memory-bound."""
        pk = peaks(device_kind)
        return max(self.flops / pk["flops_per_s"],
                   self.bytes / pk["hbm_bytes_per_s"])

    def bound(self, device_kind: str) -> str:
        pk = peaks(device_kind)
        return ("compute" if self.flops / pk["flops_per_s"]
                >= self.bytes / pk["hbm_bytes_per_s"] else "memory")


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to PEAKS with a source")
    return PEAKS[device_kind]


def _tables(layers: int, edges: int, servers: int, apps: int) -> float:
    """Bytes of one problem's DAG and environment tables, read once per
    kernel call: per layer compute, order, app and pin; per edge its
    endpoints and MB on both sides; per server power and price; per
    server pair bandwidth, price and link flag."""
    return F32 * (4 * layers + 6 * edges + 2 * servers
                  + 3 * servers * servers + apps)


def schedule_replay(layers: int, edges: int, servers: int, apps: int,
                    particles: int) -> Work:
    """Algorithm 2 for ``particles`` plans of one problem.

    Per layer: execution time (1 division), start and end (2 additions),
    the server's first start (1 min), the lease (2 additions), the app's
    completion (1 max). Per edge, seen from its child: transfer time (1
    multiply), the longest transfer or the data gate (1 max, 1 addition),
    transmission cost (1 multiply, 1 addition); seen from its parent:
    outbound time (1 multiply, 1 addition). Per server: rental (1
    subtraction, 1 multiply, 1 addition). Bytes: each plan read once,
    three results written, the tables once."""
    per_plan = 7 * layers + 7 * edges + 3 * servers
    return Work(flops=float(particles * per_plan),
                bytes=float(particles * F32 * (layers + 3)
                            + _tables(layers, edges, servers, apps)))


def traffic_replay(layers: int, edges: int, servers: int, apps: int,
                   requests: int, max_requests: int, particles: int
                   ) -> Work:
    """The queue-aware replay of one request draw for ``particles``
    plans: every layer of every request is one step. Per step as per
    layer above, plus the arrival gate (1 max); per edge per request as
    above; per request its latency and deadline test (2). Bytes: each
    plan and the draw's arrival times read once, the cost, miss count,
    latency sum and per-request latencies written, the tables once."""
    per_plan = (requests * (8 * layers + 7 * edges + 2)
                + 3 * servers)
    slots = apps * max_requests
    return Work(flops=float(particles * per_plan),
                bytes=float(particles * F32 * (layers + 3 + slots)
                            + F32 * slots
                            + _tables(layers, edges, servers, apps)))
