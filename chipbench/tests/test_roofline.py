"""Bytes a simulated step needs, against a count by hand."""

from chipbench import roofline


def test_scan_step_bytes_small_shape_by_hand():
    # F=2 flows, S=3 hop slots, e_tot=5 links, L=4 layers, 1 refinement
    # round -> 4 water-filling passes, each: ids 2*3*4=24, per-flow in
    # and out 2*8=16, link vector 5*8=40 -> 80; 4 passes = 320.
    # Flow state: 7 lanes * 4 B * 2 (read, write) * 2 flows = 112.
    # Path record: (3 + 2) * 4 B * 2 flows = 40.  Without re-rolls: 472.
    assert roofline.scan_step_bytes(2, 3, 5, 4, 1, reroute=False) == 472
    # Re-rolls add the (F, L) usable mask (8 B) and two f32 draws per
    # flow (16 B).
    assert roofline.scan_step_bytes(2, 3, 5, 4, 1, reroute=True) == 472 + 24


def test_scan_step_bytes_paper_size():
    # sf(q=19) permutation: F=10,830, S=9 (FatPaths) and 4 (ECMP),
    # e_tot = 722*29 fabric edges + 2*10,830 NIC links + 1 trash.
    assert roofline.scan_step_bytes(10830, 9, 42599, 9, 2, True) == 6170982
    assert roofline.scan_step_bytes(10830, 4, 42599, 8, 2, False) == 4470672
