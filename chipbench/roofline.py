"""Bytes one simulated transport step has to move, from shapes alone.

The count is of the algorithm, not of any implementation: the Pallas
water-filling kernel, an XLA scatter or a segment sum all have to do the
same passes over the same operands, so the roofline share it gives can
be compared across them.

One step of the flow-level scan (ndp transport, max-min water-filling
with ``fair_iters`` refinement rounds) over F flows, S hop slots per
flow (fabric hops plus the injection and ejection NIC), ``e_tot``
virtual links and L layers:

* water-filling: ``2 * (1 + fair_iters)`` passes, a scatter of per-flow
  claims onto links and a reduce of per-link shares back onto flows for
  the fair-share round and for each refinement round.  Each pass reads
  the (F, S) int32 link ids (4·F·S), reads one f32 per-flow input and
  writes one f32 per-flow output (8·F), and reads and writes one f32 per
  link (8·e_tot: the link accumulator in a scatter, loads and
  capacities in a reduce);
* flow state: the scan carries seven 4-byte lanes per flow (remaining
  bytes, layer, rate, hop count, sent and wanted accumulators, departure
  step), each read and written once per step (56·F);
* path record: the current layer's (S + 2)-int32 record per flow
  (link ids, routed flag, hop count) is gathered once per step;
* flowlet re-rolls (FatPaths, LetFlow only): the (F, L) usable-layer
  mask as bytes and two f32 draws per flow (F·L + 8·F).

The operation count is a few per byte, far below the chip's ratio of
peak operations to bandwidth, so the least time of a step is these bytes
over peak HBM bandwidth.  A pass-by-pass implementation streams exactly
these operands; one that kept a whole step's operands on chip between
passes would move fewer, which this count does not credit.
"""

from __future__ import annotations

__all__ = ["scan_step_bytes"]


def scan_step_bytes(n_flows: int, hop_slots: int, e_tot: int, n_layers: int,
                    fair_iters: int, reroute: bool) -> int:
    f, s = int(n_flows), int(hop_slots)
    passes = 2 * (1 + int(fair_iters))
    waterfill = passes * (4 * f * s + 8 * f + 8 * int(e_tot))
    state = 7 * 4 * 2 * f
    record = 4 * (s + 2) * f
    reroll = (f * int(n_layers) + 8 * f) if reroute else 0
    return waterfill + state + record + reroll
