"""Plan execution: staging assembly + repack into registered buffers.

The transport lands each :class:`ReadInterval`'s payload in a contiguous
*staging* buffer (the analogue of the RDMA landing zone — striped reads
arrive out of tensor order, from many source shards). Once every interval
of a destination transfer unit is in, ``repack`` gathers the staging
bytes into the unit's payload layout and the store absorbs it with the
ordinary ``write_unit`` path, so downstream machinery (progress counters,
pipelined readers, compact buckets) is unchanged.

Repack runs either as a NumPy scatter (the reference path the threaded
client uses by default) or through the device gather in
``repro.kernels.repack`` (``use_kernel=True``; parity is tested). The
device programs are jitted XLA, so they run compiled on whatever backend
JAX holds: the TPU on the chip, the CPU in tests.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Iterator, List, Optional, Tuple

import numpy as np

from repro.core.errors import TensorHubError
from repro.core.meta import ShardManifest, TransferUnit
from repro.obs import telemetry as obs
from repro.resharding.planner import ReadInterval, ShardPlan


@dataclasses.dataclass(frozen=True)
class PlacedInterval:
    """An interval plus where its payload lands in the unit's staging
    buffer and in the assembled unit payload."""

    interval: ReadInterval
    staging_offset: int
    unit_offset: int  # destination offset within the assembled unit payload


class ReshardExecutor:
    """Drives one destination shard's :class:`ShardPlan`."""

    def __init__(
        self,
        plan: ShardPlan,
        dest_manifest: ShardManifest,
        *,
        use_kernel: bool = False,
        recorder: Optional[obs.Recorder] = None,
    ) -> None:
        self.plan = plan
        self.manifest = dest_manifest
        self.use_kernel = use_kernel
        #: counts fused decodes per path (``decode/kernel_units`` and
        #: ``decode/host_units``)
        self.recorder = obs.DISABLED if recorder is None else recorder
        self._units: Dict[int, List[PlacedInterval]] = {}
        self._staging_bytes: Dict[int, int] = {}
        by_unit = plan.intervals_by_unit()
        for u in dest_manifest.units:
            member_off = self._member_offsets(u)
            placed: List[PlacedInterval] = []
            pos = 0
            for iv in by_unit.get(u.index, []):
                if iv.tensor not in member_off:
                    raise TensorHubError(
                        f"plan interval for {iv.tensor!r} does not belong to "
                        f"dest unit {u.index} ({u.name})"
                    )
                placed.append(
                    PlacedInterval(
                        interval=iv,
                        staging_offset=pos,
                        unit_offset=member_off[iv.tensor] + iv.dst_offset,
                    )
                )
                pos += iv.nbytes
            self._units[u.index] = placed
            self._staging_bytes[u.index] = pos

    @staticmethod
    def _member_offsets(unit: TransferUnit) -> Dict[str, int]:
        if not unit.is_compact:
            return {unit.name: 0}
        return {name: off for name, off, _ in unit.layout}

    # -- iteration --------------------------------------------------------------

    @property
    def num_units(self) -> int:
        return len(self.manifest.units)

    def unit_batches(
        self, *, start_unit: int = 0
    ) -> Iterator[Tuple[TransferUnit, List[PlacedInterval]]]:
        """Destination units in progress order, with their placed
        intervals. ``start_unit`` skips units already completed (resume
        after a source failure re-plan)."""
        for u in self.manifest.units[start_unit:]:
            yield u, self._units[u.index]

    def staging_bytes(self, dest_unit: int) -> int:
        return self._staging_bytes[dest_unit]

    def make_staging(self, dest_unit: int) -> np.ndarray:
        return np.empty(self._staging_bytes[dest_unit], dtype=np.uint8)

    # -- repack -----------------------------------------------------------------

    def instructions(self, dest_unit: int) -> List[Tuple[int, int, int]]:
        """``(staging_offset, unit_offset, nbytes)`` gather triples."""
        return [
            (p.staging_offset, p.unit_offset, p.interval.nbytes)
            for p in self._units[dest_unit]
        ]

    def repack(self, dest_unit: int, staging: np.ndarray) -> np.ndarray:
        """Assemble the destination unit's payload from staging bytes."""
        unit = self.manifest.units[dest_unit]
        instrs = self.instructions(dest_unit)
        if self.use_kernel:
            from repro.kernels.repack import repack_bytes

            return np.asarray(repack_bytes(staging, instrs, unit.nbytes))
        return repack_np(staging, instrs, unit.nbytes)

    def fused_repack(
        self, dest_unit: int, frames: List[np.ndarray]
    ) -> np.ndarray:
        """Assemble the destination unit's payload straight from int8
        *wire frames* — one frame per placed interval, in plan order —
        via the fused dequant+gather path (``kernels/quant/fused``): no
        staging-buffer decode, and the row-grid ``lead``/``tail``
        widening is dropped instead of decoded-then-discarded.

        ``use_kernel`` dispatches like :meth:`repack`: the device kernel
        for every kernel-shaped unit, the NumPy fusion otherwise. Each
        unit adds one to the recorder's ``decode/kernel_units`` or
        ``decode/host_units``, so a unit that misses the kernel shows.
        Both paths are bit-identical to decode-then-:meth:`repack`.
        """
        from repro.kernels.quant import fused as fused_lib
        from repro.transfer.codec import parse_int8_frame

        unit = self.manifest.units[dest_unit]
        placed = self._units[dest_unit]
        if len(frames) != len(placed):
            raise TensorHubError(
                f"dest unit {dest_unit}: {len(frames)} wire frames for "
                f"{len(placed)} placed intervals"
            )
        placements = []
        for p, wire in zip(placed, frames):
            iv = p.interval
            frame = parse_int8_frame(wire)
            if frame.nbytes != iv.read_nbytes:
                raise TensorHubError(
                    f"dest unit {dest_unit}: frame decodes {frame.nbytes}B "
                    f"but interval {iv.tensor}[{iv.src_offset}:"
                    f"{iv.src_stop}] read {iv.read_nbytes}B"
                )
            placements.append((frame, iv.lead, iv.nbytes, p.unit_offset))
        if self.use_kernel and fused_lib.kernel_dtype(placements, unit.nbytes):
            self.recorder.counter_add(obs.CTR_DECODE_KERNEL_UNITS, 1)
            return fused_lib.fused_repack(placements, unit.nbytes)
        self.recorder.counter_add(obs.CTR_DECODE_HOST_UNITS, 1)
        return fused_lib.fused_repack_np(placements, unit.nbytes)


def repack_np(
    staging: np.ndarray, instructions: List[Tuple[int, int, int]], out_nbytes: int
) -> np.ndarray:
    """Host reference repack: scatter staging runs into the unit payload."""
    out = np.zeros(out_nbytes, dtype=np.uint8)
    src = staging.view(np.uint8).reshape(-1)
    for s_off, d_off, nbytes in instructions:
        out[d_off : d_off + nbytes] = src[s_off : s_off + nbytes]
    return out
