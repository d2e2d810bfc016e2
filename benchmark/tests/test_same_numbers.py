"""The numbers that decide `correct` are the parent's: for both configurations
at toy widths on the CPU and seeds 4 and 5, what `compare` gives for the
program against the float32 reference, for the fp8 control and for each
planted fault, against the readings of commit 520c00d (PR 27), recorded here
from a run of that tree.  The harness may move where arrays wait (PR 28 took
the benchmark's float32 copies off the device); it may not move these."""

import pytest

import refsteps
import run

NUMBERS = ("loss_gap", "grad_norm_gap", "change_norm_gap", "grad_diff")
PARENT = [
    ("nmt-train", 4, "float32", [4.3788388178051024e-05, 0.0036691692943333367, 0.0013941002862354842, 0.010947696216604831]),
    ("nmt-train", 4, "fp8", [0.00014880598807355538, 0.017951283637307792, 0.026185362899938412, 0.0906636701865608]),
    ("nmt-train", 4, "half_batch", [0.2712078771304608, 0.4894668417701411, 0.31417384727186437, 1.147111765362383]),
    ("nmt-train", 5, "float32", [3.777147710603272e-05, 0.010047384731679033, 0.0022945357067172298, 0.010604630999970461]),
    ("nmt-train", 5, "fp8", [0.00032088612617946026, 0.03013416462162047, 0.010952188365353712, 0.09744265454813801]),
    ("nmt-train", 5, "half_batch", [0.15006231110939514, 0.5016717686101734, 0.29233723175614407, 0.7840509025125517]),
    ("transformer-train-128", 4, "float32", [0.00032594121065811427, 0.008197633409369985, 0.019385743360390558, 0.029734509050811625]),
    ("transformer-train-128", 4, "fp8", [0.004981452615217419, 0.12552749997611762, 0.05414842807140191, 0.23227780654452188]),
    ("transformer-train-128", 4, "half_batch", [0.2940437346831255, 0.5729875473238734, 0.27753176593009093, 1.2016924650502407]),
    ("transformer-train-128", 5, "float32", [0.0007193866903865438, 0.012794707378018562, 0.022335352059103544, 0.04502712631531958]),
    ("transformer-train-128", 5, "fp8", [0.00591463315575415, 0.09157211374612437, 0.05908142219290266, 0.1966315375620738]),
    ("transformer-train-128", 5, "half_batch", [0.17530542445670214, 0.3367905219007201, 0.29851689701821454, 0.7686091039596069]),
    ("nmt-train-dp4", 4, "float32", [3.991130215576305e-05, 0.003729161932193321, 0.0013965981142959286, 0.008775260588945117]),
    ("nmt-train-dp4", 4, "no_exchange", [0.15603369815200763, 0.8819062087803601, 0.4967478278653015, 1.8251471909488868]),
    ("nmt-train-dp4", 5, "float32", [3.762785932235959e-05, 0.0030081995555567274, 0.002293143039250708, 0.007397457029157068]),
    ("nmt-train-dp4", 5, "no_exchange", [0.45784201220196663, 0.3482886370247938, 0.5074637711531124, 0.9977392501812984]),
]


def _readings(cell, cfg, mix, seed, side):
    if side == "float32":  # the program as the configuration states it
        return run.program_readings(cell, cfg, mix, seed)
    if side == "fp8":
        return run.reference_readings(cell, cfg, mix, seed, precision="fp8")
    return run.reference_readings(cell, cfg, mix, seed, fault=side)


@pytest.mark.parametrize("workload,seed,side,want", PARENT,
                         ids=[f"{w}-{s}-{side}" for w, s, side, _ in PARENT])
def test_compare_gives_the_parents_numbers(workload, seed, side, want):
    _, cell, cfg, mix, _ = run.load_cell(workload, rehearsal=True)
    ref = run.reference_readings(cell, cfg, mix, seed)
    numbers, _ = refsteps.compare(_readings(cell, cfg, mix, seed, side), ref)
    assert [numbers[k] for k in NUMBERS] == pytest.approx(want, rel=1e-6, abs=0.0)
