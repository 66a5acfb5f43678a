"""The control, at smoke size on a CPU: the reference in int8 put in the
program's place reads a gap above the smoke limit, where the program (in
float32 at this size, as the reference) reads one within it."""
from chipbench import control, harness, loadgen


def test_the_int8_control_fails_the_limit():
    spec = harness.load_spec()
    for config in ("olmo_1b", "olmo_1b_phi"):
        c = harness.load_config(config)
        cell = harness.Cell(name=f"{config}.control", chips=1, config=c,
                            traffic=loadgen.load("chat_closed"),
                            end_to_end=spec["end_to_end"], per_layer=[],
                            ref=harness.load_reference(c))
        r = control.readings(cell, 2 ** 31 + 23, 1.0, smoke=True)
        limit = harness.sizes(c, True)["correct"]["max_logit_gap"]
        assert r["tokens"] > 0
        assert r["program"] <= limit, r
        assert r["control"] > limit, r

