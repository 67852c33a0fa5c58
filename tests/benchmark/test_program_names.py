"""Programs that carry their own name need no join: since the serving
forwards are jitted under the names the engine dispatches them by, a
trace's ``XLA Modules`` read ``jit_decode_forward(<hash>)``, and
``trace.program_names`` names them whether or not the traced window began
and ended with the device drained (launches = executions), which the join
for a ``jit__unknown`` needs."""
from benchmark import trace

PLANE = "/device:TPU:0"
KERNEL = ('%paged_decode.3 = bf16[32,1,32,128]{3,2,1,0} custom-call(), '
          'custom_call_target="tpu_custom_call"')


def a_trace(launches):
    modules = [["jit_decode_forward(11)", 1.0, 0.05],
               ["jit_dynamic_slice(7)", 1.06, 1e-6],
               ["jit_ragged_forward(13)", 2.0, 0.11],
               ["jit__unknown(17)", 3.0, 0.05]]
    ops = [[KERNEL, 1.01, 0.001], [KERNEL, 2.01, 0.004],
           ["%fusion.9 = f32[8]{0} fusion()", 3.01, 0.01]]
    host = [["PjitFunction(decode_forward)", 0.99, 0.001],
            ["PjitFunction(dynamic_slice)", 1.055, 0.001],
            ["PjitFunction(ragged_forward)", 1.99, 0.001],
            ["PjitFunction(train_batch_fn)", 2.99, 0.001]]
    host += [[trace.LAUNCH, s, 1e-4]
             for s in (0.9905, 1.0555, 1.9905, 2.9905)[:launches]]
    return {"devices": {PLANE: {"modules": modules, "ops": ops}},
            "host": sorted(host, key=lambda e: e[1])}


def test_named_modules_are_named_when_launches_and_modules_differ():
    tr = a_trace(launches=3)     # a launch fell outside the trace
    names = trace.program_names(tr, PLANE)
    assert names["jit_decode_forward(11)"] == "decode_forward"
    assert names["jit_ragged_forward(13)"] == "ragged_forward"
    assert names["jit__unknown(17)"] == "unknown"    # only the join names it
    assert trace.program_times(tr, PLANE, "decode_forward") == [0.05]
    # ... and the breakdown tells the paged kernel from the ragged one by
    # the program it ran in, from the module names alone
    top = dict(trace.top_ops(tr, PLANE))
    assert top["decode_forward/kernel:paged_decode.3"] == 0.001
    assert top["ragged_forward/kernel:paged_decode.3"] == 0.004


def test_the_join_still_names_an_unnamed_module_when_they_pair():
    names = trace.program_names(a_trace(launches=4), PLANE)
    assert names["jit__unknown(17)"] == "train_batch_fn"
    assert names["jit_decode_forward(11)"] == "decode_forward"
