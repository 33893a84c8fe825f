"""The FLOP and byte functions of work/ against hand counts."""
import harness
from hlo import CustomCall, Shape


def f32(*dims):
    return Shape("f32", tuple(dims))


def s32(*dims):
    return Shape("s32", tuple(dims))


def syr2k(n, k, batch=()):
    mats = [f32(*batch, n, k)] * 4 + [f32(*batch, n, n)]
    return CustomCall("syr2k_lower.1", "syr2k_lower", (f32(*batch, n, n),), (s32(3), s32(3), *mats))


def test_syr2k_hand_count():
    # n = 2, k = 1: 3 lower entries, each two products of length 1 -> 2 FLOPs
    # (multiply-adds counted as 2): 3 * 2 * 2 = 12; bytes 4 * (2*2*1 + 2*3) = 40.
    assert harness.load_module("work", "syr2k_lower").work(syr2k(2, 1)) == (12, 40)


def test_syr2k_stage_shape_and_batch():
    flops, nbytes = harness.load_module("work", "syr2k_lower").work(syr2k(3840, 256))
    assert flops == 2 * 256 * 3840 * 3841
    assert nbytes == 4 * (2 * 3840 * 256 + 3840 * 3841)
    fb, bb = harness.load_module("work", "syr2k_lower").work(syr2k(3840, 256, batch=(32,)))
    assert (fb, bb) == (32 * flops, 32 * nbytes)

