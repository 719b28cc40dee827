"""The control of the served cells' check, at a size a test run holds.

The control is the reference computed with int8 matmuls, put in the
program's place: at each position of the prompts and served tokens of a
run's sample, the token it puts first is read against the f32
reference.  On the chip, at the cells' own sizes, it reads several times
the program's mean gap (``PERF.md``).  Here, on the CPU at smoke size
(d_model 64), few tokens flip either way and one seed can read either
side, so the readings are taken as the limit's are, over seeds: the
control's mean gap over three seeds must exceed the program's.  Both
readings come from ``correct.check_served``, the check a run makes."""
import pytest

from bench_smoke import smoke_ctx


@pytest.mark.parametrize("workload", ["mamba2-chat-burst", "mamba2-chat-steady"])
def test_int8_control_reads_wider_than_the_program(workload):
    from bench import correct
    from bench.drivers import serve

    program, control = [], []
    for seed in (2**31 + 1, 2**31 + 2, 2**31 + 3):
        _, ctx = smoke_ctx(workload, seed)
        server = serve._build(ctx)
        serve._warm(server, ctx.mix, ctx.arch.vocab_size)
        w = serve.window(server, ctx)
        del server
        res = correct.check_served(ctx, w["served"], controls=("int8",))
        program.append(res["checks"][0][1])
        control.append(res["controls"]["int8"]["checks"][0][1])
        assert res["correct"] and program[-1] < float("inf") and control[-1] < float("inf")
    assert max(control) > max(program), (control, program)
