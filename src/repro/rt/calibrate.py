"""Cold-start PCIe link calibration: measure, fit, then serve.

The swap terms of the ``TimeModel`` (``swap_byte``/``swap_floor``/
``swap_launch``) price every swap-vs-recompute decision and every SLO
charge for carried transfer traffic — but the presets are nominal link
numbers (PCIe 4.0/5.0 x16). A server should not price a link it never
measured: at startup, ``serve --serve`` runs a few real
``jax.device_put``/``device_get`` round trips, fits the byte rate and
dispatch floor with ``TimeModel.fit_swap``, and (optionally) overlaps a
transfer with a jitted matmul to recover the async-copy launch overhead
via ``TimeModel.fit_swap_overlap`` — all before the first request is
admitted.

A degenerate fit (zero byte rate) on the CPU backend, where "device"
transfers are memcpys that alias host memory, leaves the preset terms
untouched and reports why. Anywhere else a failed measurement or a
degenerate fit raises: a server does not start on a link it could not
measure.
"""
from __future__ import annotations

import logging
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

logger = logging.getLogger(__name__)

# modest payloads: enough spread for a 2-term lstsq, small enough that
# startup stays sub-second even over a slow link
DEFAULT_SIZES = (1 << 18, 1 << 20, 1 << 22)      # 256 KiB, 1 MiB, 4 MiB


@dataclass
class LinkCalibration:
    """Outcome of one cold-start calibration run."""
    applied: bool                      # did the fit replace the presets?
    backend: str                       # jax platform name, or "unavailable"
    swap_byte: float                   # the model's terms after the run
    swap_floor: float
    swap_launch: float
    samples: List[Tuple[int, float]] = field(default_factory=list)
    overlap_samples: List[Tuple[float, int, float]] = \
        field(default_factory=list)
    error: Optional[str] = None

    @property
    def bandwidth_gbs(self) -> Optional[float]:
        """Fitted effective link bandwidth, GB/s."""
        if self.swap_byte <= 0.0:
            return None
        return 1.0 / (self.swap_byte * 1e9)

    def summary(self) -> str:
        if not self.applied:
            return (f"link calibration skipped ({self.error}); "
                    f"keeping preset swap terms")
        bw = self.bandwidth_gbs
        return (f"link calibrated on {self.backend}: "
                f"{bw:.1f} GB/s effective, floor {self.swap_floor*1e6:.0f}us, "
                f"launch {self.swap_launch*1e6:.0f}us "
                f"({len(self.samples)} transfer samples)")


def measure_link(sizes=DEFAULT_SIZES,
                 repeats: int = 3) -> List[Tuple[int, float]]:
    """Time real host->device and device->host transfers. Returns
    ``(n_bytes, seconds)`` samples (both directions pooled — the fit
    recovers one effective link rate)."""
    samples: List[Tuple[int, float]] = []
    for n in sizes:
        buf = np.zeros(n, dtype=np.uint8)
        # one unmeasured round trip per size: allocator/compile warm-up
        dev = jax.block_until_ready(jax.device_put(buf))
        jax.device_get(dev)
        for _ in range(repeats):
            t0 = time.perf_counter()
            dev = jax.block_until_ready(jax.device_put(buf))
            samples.append((n, time.perf_counter() - t0))
            t0 = time.perf_counter()
            jax.device_get(dev)
            samples.append((n, time.perf_counter() - t0))
    return samples


def measure_overlap(tm, sizes=DEFAULT_SIZES, repeats: int = 2,
                    matmul_dim: int = 512) -> List[Tuple[float, int, float]]:
    """Overlap a ``device_put`` (issued from a helper thread) with a jitted
    matmul and time the pair — ``(compute_s, n_bytes, total_s)`` samples
    for ``fit_swap_overlap``'s max-plus-launch residual."""
    x = jnp.ones((matmul_dim, matmul_dim), jnp.float32)
    step = jax.jit(lambda a: a @ a)
    jax.block_until_ready(step(x))                 # compile
    t0 = time.perf_counter()
    jax.block_until_ready(step(x))
    compute_s = time.perf_counter() - t0
    samples: List[Tuple[float, int, float]] = []
    with ThreadPoolExecutor(max_workers=1) as pool:
        for n in sizes:
            buf = np.zeros(n, dtype=np.uint8)
            jax.block_until_ready(jax.device_put(buf))   # warm-up
            for _ in range(repeats):
                t0 = time.perf_counter()
                fut = pool.submit(
                    lambda b=buf: jax.block_until_ready(jax.device_put(b)))
                jax.block_until_ready(step(x))
                fut.result()
                samples.append((compute_s, n, time.perf_counter() - t0))
    return samples


def calibrate_link(tm, *, sizes=DEFAULT_SIZES, repeats: int = 3,
                   overlap: bool = True) -> LinkCalibration:
    """Measure the real link and refit ``tm``'s swap terms in place.

    On the CPU backend a degenerate fit (non-positive byte rate: device
    buffers alias host memory) restores the model's preset terms and the
    returned record says why. On an accelerator it raises, as does any
    failed measurement."""
    snapshot = (tm.swap_byte, tm.swap_floor, tm.swap_launch)
    backend = jax.default_backend()
    samples = measure_link(sizes, repeats)
    tm.fit_swap(samples)
    # a fitted rate implying > ~1 PB/s is float noise from size-blind
    # timings: nothing real was measured
    if tm.swap_byte < 1e-15:
        tm.swap_byte, tm.swap_floor, tm.swap_launch = snapshot
        reason = "degenerate fit: measured byte rate ~ 0"
        if backend != "cpu":
            raise RuntimeError(f"link calibration on {backend}: {reason}")
        return LinkCalibration(applied=False, backend=backend,
                               swap_byte=tm.swap_byte,
                               swap_floor=tm.swap_floor,
                               swap_launch=tm.swap_launch, error=reason)
    overlap_samples: List[Tuple[float, int, float]] = []
    if overlap:
        overlap_samples = measure_overlap(tm, sizes)
        tm.fit_swap_overlap(overlap_samples)
    cal = LinkCalibration(applied=True, backend=backend,
                          swap_byte=tm.swap_byte,
                          swap_floor=tm.swap_floor,
                          swap_launch=tm.swap_launch,
                          samples=samples,
                          overlap_samples=overlap_samples)
    logger.info("%s", cal.summary())
    return cal
