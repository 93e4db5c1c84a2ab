"""Roofline-term derivation from a compiled dry-run artifact (§Roofline).

Hardware model: TPU v5e — 197 TFLOP/s bf16 per chip, 819 GB/s HBM,
~50 GB/s/link ICI, 16 GB HBM per chip.

All three terms come from the *per-device* SPMD program:

    compute term    = flops_per_device / peak_flops
    memory term     = bytes_per_device / hbm_bw
    collective term = wire_bytes_per_device / link_bw

FLOPs/bytes/wire are parsed from the optimized HLO by ``hlo_stats`` rather
than taken from ``compiled.cost_analysis()``: cost_analysis (a) visits a
``while`` body once — scanned-layer models would be undercounted
~n_layers-fold (verified empirically) — and (b) does not expose collective
bytes at all. The raw cost_analysis numbers are recorded alongside for
reference. Both sources describe the partitioned per-device module
(verified: an 8-way sharded matmul reports total/8 flops).
"""

from __future__ import annotations

import dataclasses

from repro.launch.hlo_stats import hlo_stats

V5E = {
    "peak_flops": 197e12,     # bf16 FLOP/s per chip
    "hbm_bw": 819e9,          # bytes/s per chip
    "link_bw": 50e9,          # bytes/s per ICI link
    "hbm_bytes": 16e9,        # HBM capacity per chip
}


@dataclasses.dataclass
class Roofline:
    flops_per_device: float
    bytes_per_device: float
    wire_bytes_per_device: float
    t_compute: float
    t_memory: float
    t_collective: float
    bottleneck: str
    model_flops: float | None = None   # 6ND-style useful FLOPs (global)
    useful_ratio: float | None = None  # model_flops / (flops * n_chips)
    collectives: dict | None = None
    memory: dict | None = None

    @property
    def t_bound(self) -> float:
        """Lower-bound step time if the dominant term were perfectly
        overlapped with everything else."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    def roofline_fraction(self) -> float | None:
        """Useful-compute fraction of the dominant-term-bound step time."""
        if self.model_flops is None or self.t_bound == 0:
            return None
        n_chips = (self.model_flops / self.useful_ratio / self.flops_per_device
                   if self.useful_ratio else None)
        if not n_chips:
            return None
        ideal = self.model_flops / (n_chips * V5E["peak_flops"])
        return ideal / self.t_bound

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["t_bound"] = self.t_bound
        frac = self.roofline_fraction()
        if frac is not None:
            d["roofline_fraction"] = frac
        return d


def analyze(compiled, n_chips: int, model_flops: float | None = None,
            hw: dict = V5E) -> Roofline:
    """Derive the three roofline terms from a compiled executable."""
    ca = compiled.cost_analysis()
    stats = hlo_stats(compiled.as_text(), n_chips)
    flops = float(stats["flops"])
    bytes_acc = float(stats["bytes"])
    coll = stats
    wire = float(coll["total"]["wire_bytes"])
    xla_flops = float(ca.get("flops", 0.0) or 0.0)
    xla_bytes = float(ca.get("bytes accessed", 0.0) or 0.0)

    t_compute = flops / hw["peak_flops"]
    t_memory = bytes_acc / hw["hbm_bw"]
    t_collective = wire / hw["link_bw"]
    # CPU artifact: XLA:CPU promotes bf16 reductions to f32 (reducer named
    # "_promoted") — on TPU those collectives move half the bytes.
    promoted = float(stats.get("promoted_wire_bytes", 0.0))
    wire_tpu = wire - promoted / 2.0
    bottleneck = max(
        (("compute", t_compute), ("memory", t_memory),
         ("collective", t_collective)), key=lambda kv: kv[1])[0]

    mem = None
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            mem = {
                "argument_bytes": int(ma.argument_size_in_bytes),
                "output_bytes": int(ma.output_size_in_bytes),
                "temp_bytes": int(ma.temp_size_in_bytes),
                "alias_bytes": int(ma.alias_size_in_bytes),
                "peak_bytes": int(ma.argument_size_in_bytes
                                  + ma.output_size_in_bytes
                                  + ma.temp_size_in_bytes
                                  - ma.alias_size_in_bytes),
                "fits_hbm": bool(
                    ma.argument_size_in_bytes + ma.output_size_in_bytes
                    + ma.temp_size_in_bytes - ma.alias_size_in_bytes
                    < hw["hbm_bytes"]),
            }
    except Exception:                                  # pragma: no cover
        pass

    useful = None
    if model_flops:
        useful = model_flops / max(flops * n_chips, 1.0)
    if mem is None:
        mem = {}
    mem["xla_flops"] = xla_flops
    mem["xla_bytes"] = xla_bytes
    # CPU-backend artifact: hoisted bf16->f32 weight upcasts (XLA CPU has no
    # native bf16 dot). Subtracting gives the TPU-faithful residency.
    mem["cpu_upcast_bytes"] = float(stats.get("entry_upcast_bytes", 0.0))
    mem["promoted_wire_bytes"] = promoted
    mem["wire_tpu_estimate"] = wire_tpu
    mem["t_collective_tpu"] = wire_tpu / hw["link_bw"]
    if "peak_bytes" in mem:
        tpu_peak = mem["peak_bytes"] - mem["cpu_upcast_bytes"]
        mem["tpu_peak_estimate"] = tpu_peak
        mem["fits_hbm_tpu"] = bool(tpu_peak < hw["hbm_bytes"])
    return Roofline(
        flops_per_device=flops, bytes_per_device=bytes_acc,
        wire_bytes_per_device=wire, t_compute=t_compute, t_memory=t_memory,
        t_collective=t_collective, bottleneck=bottleneck,
        model_flops=model_flops, useful_ratio=useful,
        collectives=coll["per_op"], memory=mem)
