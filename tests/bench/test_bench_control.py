"""The controls, at a size a test run holds: the plain reference in the
program's place at the precision below the configuration's comes out
not correct, where the program does."""

from bench import control


def test_mapper_bfloat16_control_fails_the_exact_scores(tiny):
    config, mix = tiny("map-ont")
    [(_, program, low)] = control.readings("map-ont", [2**31 + 3], 2.0,
                                           config=config, mix=mix)
    limits = config["limits"]
    assert all(program[k] <= limits[k] for k in limits if k !=
               "misplaced_share")
    assert low["sw_score_gap"] > limits["sw_score_gap"]


def test_lm_int8_control_reads_above_the_program(tiny):
    """At the tiny width int8 and bf16 rounding are of one size, so a
    single seed may read either way (14 seeds on the CPU: the program
    0-0.016, the control 0.004-0.026); over three seeds the control's
    median reads above the program's."""
    from statistics import median

    config, mix = tiny("rwkv-docs")
    got = control.readings("rwkv-docs", [2**31 + 4, 2**31 + 5, 2**31 + 6],
                           2.0, config=config, mix=mix)
    program = [p["logit_gap_max"] for _, p, _ in got]
    low = [c["logit_gap_max"] for _, _, c in got]
    assert max(program) <= config["limits"]["logit_gap_max"]
    assert median(low) > median(program)
