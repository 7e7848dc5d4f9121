"""Three-term roofline from a compiled (AOT) step.

All primary numbers come from the structural HLO analyzer
(`repro.roofline.hlo.analyze_entry`), which multiplies loop bodies by their
trip counts — XLA's own ``cost_analysis()`` counts each ``while`` body once,
which under-reports scanned layers by ~n_layers; its raw numbers are kept in
the report for transparency.

Post-SPMD HLO shapes are PER-DEVICE, so analyzer outputs are per-chip,
each term over the target device's published peak (:data:`PEAKS`, keyed
by ``jax.Device.device_kind``; a kind not in the table is an error):

    compute    = flops_per_chip / peak FLOP/s (bf16)
    memory     = hbm_bytes_per_chip / peak HBM bytes/s
    collective = collective_bytes_per_chip / ICI bytes/s of one link

MODEL_FLOPS = 6 N D (dense) / 6 N_active D (MoE); the two-stream algorithms
add a frozen-global forward (+2 N D) which we count in MODEL_FLOPS_2STREAM
so the useful-ratio separates genuine technique overhead from waste.
"""
from __future__ import annotations

from dataclasses import asdict, dataclass, field
from typing import Dict, Optional

from repro.configs.base import ArchConfig, InputShape
from repro.launch.specs import fl_plan
from repro.roofline.hlo import analyze_entry



@dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip peaks of one device kind."""
    flops: float        # bf16 FLOP/s
    hbm_bw: float       # HBM bytes/s
    ici_bw: float       # bytes/s of one inter-chip link
    source: str


PEAKS: Dict[str, DevicePeaks] = {
    # 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s of interconnect over
    # the chip's four ICI links (50 GB/s each)
    "TPU v5 lite": DevicePeaks(
        flops=197e12, hbm_bw=819e9, ici_bw=50e9,
        source='Google Cloud documentation, "TPU v5e"'),
}


def device_peaks(device_kind: str) -> DevicePeaks:
    """The :data:`PEAKS` entry of ``device_kind``; unknown kinds raise."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") \
            from None


@dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    device_kind: str
    flops_per_chip: float
    hbm_bytes_per_chip: float
    coll_bytes_per_chip: float
    coll_breakdown: Dict[str, float] = field(default_factory=dict)
    coll_counts: Dict[str, float] = field(default_factory=dict)
    model_flops: float = 0.0
    model_flops_2stream: float = 0.0
    xla_cost_flops: float = 0.0       # raw cost_analysis (loop bodies x1)
    xla_cost_bytes: float = 0.0
    peak_memory_bytes: float = 0.0

    @property
    def peaks(self) -> DevicePeaks:
        return device_peaks(self.device_kind)

    @property
    def t_compute(self) -> float:
        return self.flops_per_chip / self.peaks.flops

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes_per_chip / self.peaks.hbm_bw

    @property
    def t_collective(self) -> float:
        return self.coll_bytes_per_chip / self.peaks.ici_bw

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_ratio(self) -> float:
        total = self.flops_per_chip * self.chips
        return self.model_flops / total if total else 0.0

    @property
    def mfu_bound(self) -> float:
        """Upper bound on MFU implied by the dominant term."""
        t = max(self.t_compute, self.t_memory, self.t_collective)
        if t <= 0:
            return 0.0
        return self.model_flops / (t * self.chips * self.peaks.flops)

    def to_dict(self) -> Dict:
        d = asdict(self)
        d.update(t_compute=self.t_compute, t_memory=self.t_memory,
                 t_collective=self.t_collective, bottleneck=self.bottleneck,
                 useful_ratio=self.useful_ratio, mfu_bound=self.mfu_bound)
        return d


def model_flops(cfg: ArchConfig, shape: InputShape, mesh,
                two_stream: bool = True) -> Dict[str, float]:
    n = cfg.active_param_count()
    if shape.kind == "train":
        plan = fl_plan(cfg, shape, mesh)
        tokens = (plan.n_clients * plan.local_steps * plan.client_batch
                  * shape.seq_len)
        base = float(6 * n * tokens)
        return {"model_flops": base,
                "model_flops_2stream": base + (2.0 * n * tokens
                                               if two_stream else 0.0)}
    if shape.kind == "prefill":
        f = float(2 * n * shape.global_batch * shape.seq_len)
    else:
        f = float(2 * n * shape.global_batch)   # decode: 1 token/seq
    return {"model_flops": f, "model_flops_2stream": f}


def analyze(compiled, cfg: ArchConfig, shape: InputShape, mesh_name: str,
            chips: int, mesh=None, two_stream: bool = True, *,
            device_kind: str) -> Roofline:
    """Roofline of ``compiled`` against the peaks of ``device_kind`` (the
    chip the step is meant for, not the device it was compiled on)."""
    device_peaks(device_kind)
    text = compiled.as_text()
    cost = analyze_entry(text)

    xla_cost = compiled.cost_analysis()
    if isinstance(xla_cost, list):
        xla_cost = xla_cost[0]

    peak = 0.0
    try:
        ma = compiled.memory_analysis()
        peak = float(getattr(ma, "temp_size_in_bytes", 0) +
                     getattr(ma, "argument_size_in_bytes", 0))
    except Exception:
        pass

    mf = model_flops(cfg, shape, mesh, two_stream)
    return Roofline(
        arch=cfg.name, shape=shape.name, mesh=mesh_name, chips=chips,
        device_kind=device_kind,
        flops_per_chip=cost.flops,
        hbm_bytes_per_chip=cost.bytes,
        coll_bytes_per_chip=cost.total_coll_bytes,
        coll_breakdown=dict(cost.coll_bytes),
        coll_counts=dict(cost.coll_counts),
        model_flops=mf["model_flops"],
        model_flops_2stream=mf["model_flops_2stream"],
        xla_cost_flops=float(xla_cost.get("flops", 0.0)),
        xla_cost_bytes=float(xla_cost.get("bytes accessed", 0.0)),
        peak_memory_bytes=peak)
