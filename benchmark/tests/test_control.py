"""The control comes out not correct: the plain reference put in the
program's place and computed one precision below the configuration's fails
at least one of the cell's numbers, at a size a test run can hold. (A tiny
bfloat16 model is all round-off, so the rehearsal holds the configuration in
float32 and its control is the step below that, bfloat16; on the chip the
cells are bfloat16 and the control fp8 -- those readings are in PERF.md.) So
does the reference with half of the batch left out."""

import json


from conftest import bench


def control_of(workload, tmp_path):
    r = bench("--workload", workload, "--seed", "41", "--seconds", "1",
              "--trace", "0", "--rehearse", "--control", "--out", str(tmp_path))
    assert r.returncode == 0, r.stderr[-3000:]
    line = json.loads(r.stdout.strip().splitlines()[-1])
    limits = {k: c["limit"] for k, c in line["compared"].items()}
    return line, limits


def test_train_control_and_half_batch_fail(tmp_path):
    line, limits = control_of("mistral7b_train", tmp_path)
    assert line["correct"] is True, line["compared"]
    for name in ("bfloat16", "half_batch"):
        over = [k for k, v in line["control"][name].items()
                    if k in limits and v > limits[k]]
        assert over, (name, line["control"][name], limits)
