"""ZN540-calibrated analytic performance model.

We cannot measure a real ZNS SSD in this environment, so the paper's own
measurements (§2.2, Figure 2; Exp#1 Figure 6; Exp#3 Figure 8) are used as
the calibration surface for an analytic throughput/latency model.  The
discrete-event drive model (:mod:`repro.sim.device`) samples its service
times from these means, and the model must reproduce the paper's
qualitative trends (``tests/test_distributed.py``):

* Zone Append > Zone Write for small writes on few open zones (intra-zone
  parallelism, saturating at ~4 outstanding appends per zone);
* Zone Write scales better with many open zones (inter-zone parallelism);
  Zone Append degrades beyond ~2 open zones (firmware compute);
* 16 KiB writes saturate a zone under either primitive;
* group size G buys Zone-Append concurrency up to qd saturation, with a
  per-group barrier amortized over G stripes.

All throughputs in MiB/s, latencies in microseconds, sizes in KiB.
"""
from __future__ import annotations

import bisect
import dataclasses

# ---- calibration points (paper §2.2 / Figure 2) ---------------------------

# Zone Write: single-zone throughput per request size (one outstanding cmd).
ZW_SINGLE = {4: 337.6, 8: 613.6, 16: 1050.0}
# Zone Write: device-level ceiling with many open zones.
ZW_DEVICE_MAX = {4: 777.1, 8: 1430.7, 16: 1750.0}
# Zone Append: single-zone throughput at qd>=4 (saturated intra-zone parallelism)
ZA_SINGLE_SAT = {4: 541.5, 8: 1026.6, 16: 1050.1}
# Zone Append: device ceiling (peaks at ~2 open zones, then firmware-bound)
ZA_DEVICE_MAX = {4: 577.5, 8: 1058.6, 16: 1750.0}
# Zone Append firmware penalty per extra open zone beyond 2 (fractional loss
# applied to the aggregate; paper Fig. 2a shows 4 KiB ZA dropping below its
# 2-zone peak as more zones open)
ZA_MULTIZONE_PENALTY = {4: 0.035, 8: 0.03, 16: 0.0}
ZA_SATURATION_QD = 4

_SIZES = sorted(ZW_SINGLE)


def _interp(table: dict[int, float], size_kib: float) -> float:
    """Log-linear interpolation over the calibrated request sizes."""
    sizes = _SIZES
    if size_kib <= sizes[0]:
        return table[sizes[0]] * (size_kib / sizes[0])  # latency-bound region
    if size_kib >= sizes[-1]:
        return table[sizes[-1]]  # bandwidth-saturated region
    i = bisect.bisect_left(sizes, size_kib)
    lo, hi = sizes[i - 1], sizes[i]
    f = (size_kib - lo) / (hi - lo)
    return table[lo] * (1 - f) + table[hi] * f


def zone_write_tput(size_kib: float, n_zones: int = 1) -> float:
    """Aggregate Zone Write throughput over ``n_zones`` open zones."""
    per_zone = _interp(ZW_SINGLE, size_kib)
    ceiling = _interp(ZW_DEVICE_MAX, size_kib)
    return min(per_zone * max(1, n_zones), ceiling)


def zone_append_tput(size_kib: float, qd: int = 4, n_zones: int = 1) -> float:
    """Aggregate Zone Append throughput (qd = outstanding appends per zone)."""
    sat = _interp(ZA_SINGLE_SAT, size_kib)
    base = _interp(ZW_SINGLE, size_kib)  # qd=1 behaves like an ordered write
    eff_qd = min(max(1, qd), ZA_SATURATION_QD)
    per_zone = base + (sat - base) * (eff_qd - 1) / (ZA_SATURATION_QD - 1)
    ceiling = _interp(ZA_DEVICE_MAX, size_kib)
    penalty = _interp(ZA_MULTIZONE_PENALTY, size_kib)
    agg = min(per_zone * max(1, n_zones), ceiling)
    agg *= 1.0 - penalty * max(0, n_zones - 2)  # firmware compute penalty
    return max(agg, 0.05 * per_zone)


# ---- per-command latency (timed simulation, repro.sim) ---------------------
#
# The discrete-event engine needs *service times for individual commands*,
# not aggregate throughputs.  These are derived from the same ZN540
# calibration surface: in the latency-bound region a zone sustains
# ``tput = size / latency`` with one outstanding command, so the calibrated
# single-zone throughput curve *is* a latency curve.  Zone Append reaches
# its saturated throughput with ~4 commands in flight, so its per-command
# service time at queue depth qd satisfies ``qd * size / latency = tput(qd)``.


def zone_write_cmd_latency_us(size_kib: float) -> float:
    """Mean service time of one Zone Write command (one outstanding/zone)."""
    return size_kib / 1024.0 / zone_write_tput(size_kib, 1) * 1e6


def zone_append_cmd_latency_us(size_kib: float, qd: int = ZA_SATURATION_QD) -> float:
    """Mean service time of one Zone Append command at in-flight depth ``qd``.

    At qd=1 this equals the Zone Write latency (an append with no siblings is
    an ordered write); at qd>=4 the intra-zone parallelism is saturated and
    per-command latency grows while aggregate throughput plateaus -- exactly
    the Figure 2 shape."""
    eff = min(max(1, qd), ZA_SATURATION_QD)
    return eff * size_kib / 1024.0 / zone_append_tput(size_kib, eff, 1) * 1e6


def read_cmd_latency_us(size_kib: float) -> float:
    """Mean service time of one read command (NAND page read dominated).

    Calibrated to the paper's ~82-86 us normal-read medians at 4 KiB
    (Figure 7); reads are slower than SLC-buffered writes on the ZN540."""
    return 70.0 + 4.0 * size_kib


@dataclasses.dataclass
class ArrayPerf:
    """Array-level write performance estimate."""
    throughput_mib_s: float
    median_lat_us: float
    p95_lat_us: float


def zapraid_write_perf(
    *,
    k: int,
    m: int,
    chunk_kib: float,
    group_size: int,
    host_qd: int = 64,
    n_open_segments: int = 1,
    use_append: bool = True,
    barrier_us: float = 12.0,
) -> ArrayPerf:
    """Estimated ZapRAID write throughput for one segment class.

    The user-visible throughput counts data chunks only (k of k+m); the
    drives carry chunk-sized requests.  Zone-Append concurrency per zone is
    bounded by both the stripe-group size G and the host queue depth; the
    inter-group barrier costs ``barrier_us`` amortized over G stripes.
    """
    n = k + m
    per_zone_qd = max(1, min(group_size, host_qd // max(1, n_open_segments)))
    if use_append and group_size > 1:
        drive_tput = zone_append_tput(chunk_kib, per_zone_qd, n_open_segments)
    else:
        # One outstanding Zone Write per zone serializes stripe commits; the
        # paper measures ~10% loss vs the ideal 3x single-zone rate (Exp#1:
        # 910.8 vs 1012.8 MiB/s for 4 KiB).
        drive_tput = zone_write_tput(chunk_kib, n_open_segments) * 0.90
    # Each drive sustains drive_tput; stripes need all k+m chunks; user data
    # fraction is k/(k+m).
    raw = drive_tput * n
    user = raw * (k / n)
    if use_append and group_size > 1 and barrier_us > 0:
        # Barrier amortization: G stripes of k*chunk user bytes per barrier.
        group_bytes_mib = group_size * k * chunk_kib / 1024.0
        t_group_s = group_bytes_mib / user + barrier_us * 1e-6
        user = group_bytes_mib / t_group_s
    stripe_kib = k * chunk_kib
    med = stripe_kib / 1024.0 / max(user, 1e-9) * 1e6  # us per stripe
    p95_factor = 3.0 if (use_append and chunk_kib >= 16) else 1.8
    return ArrayPerf(
        throughput_mib_s=user,
        median_lat_us=med,
        p95_lat_us=med * p95_factor,
    )


def hybrid_write_perf(
    *,
    k: int,
    m: int,
    cs_kib: float,
    cl_kib: float,
    n_small: int,
    n_large: int,
    frac_small: float,
    group_size: int,
    host_qd: int = 64,
) -> ArrayPerf:
    """Hybrid data management (§3.3): small writes -> N_s small-chunk segments
    (one reserved for Zone Append), large writes -> N_l Zone-Write segments."""
    n_open = max(1, n_small + n_large)
    perfs = []
    if frac_small > 0 and n_small > 0:
        za = zapraid_write_perf(
            k=k, m=m, chunk_kib=cs_kib, group_size=group_size,
            host_qd=host_qd, n_open_segments=1, use_append=True,
        )
        zw_small = (
            zapraid_write_perf(
                k=k, m=m, chunk_kib=cs_kib, group_size=1,
                host_qd=host_qd, n_open_segments=n_small - 1, use_append=False,
            ).throughput_mib_s
            if n_small > 1
            else 0.0
        )
        perfs.append(("small", frac_small, za.throughput_mib_s + zw_small, za))
    if frac_small < 1 and n_large > 0:
        zw = zapraid_write_perf(
            k=k, m=m, chunk_kib=cl_kib, group_size=1,
            host_qd=host_qd, n_open_segments=n_large, use_append=False,
        )
        perfs.append(("large", 1 - frac_small, zw.throughput_mib_s, zw))
    if not perfs:  # everything routed to whatever class exists
        za = zapraid_write_perf(
            k=k, m=m, chunk_kib=cs_kib if n_small else cl_kib,
            group_size=group_size if n_small else 1, host_qd=host_qd,
            n_open_segments=n_open, use_append=bool(n_small),
        )
        return za
    # classes run concurrently; workload completes when the slower class
    # finishes its share: T = max_i share_i / tput_i; overall = 1 / T.
    t_total = max(share / max(tput, 1e-9) for _, share, tput, _ in perfs)
    tput = 1.0 / t_total
    med = sum(share * p.median_lat_us for _, share, _, p in perfs)
    p95 = max(p.p95_lat_us for _, _, _, p in perfs)
    return ArrayPerf(throughput_mib_s=tput, median_lat_us=med, p95_lat_us=p95)


def degraded_read_latency_us(
    *, k: int, chunk_kib: float, group_size: int, cst_entry_ns: float = 4.0
) -> float:
    """Degraded read latency: k parallel chunk reads + decode + CST search.

    CST query touches k*G entries (§3.2); read latency calibrated to the
    paper's ~85 us medians (Figure 7) for 4 KiB chunks."""
    read_us = 70.0 + 4.0 * chunk_kib  # k reads issued in parallel
    decode_us = 0.4 * chunk_kib * k / 3.0
    query_us = (k * group_size * cst_entry_ns) / 1e3
    return read_us + decode_us + query_us
