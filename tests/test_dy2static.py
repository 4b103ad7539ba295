"""AST-based to_static: data-dependent control flow exports for real.

Parity model: reference dygraph_to_static (program_translator.py,
ifelse_transformer.py, loop_transformer.py,
break_continue_transformer.py) — a dygraph function with python
``if``/``while``/``for`` over tensor values must export a static
program whose cond/while OPS reproduce eager outputs on BOTH branches
and at data-dependent trip counts, through TracedLayer and the
inference Predictor.
"""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import dygraph
from paddle_tpu.dygraph import jit as djit
from paddle_tpu.dygraph.tensor import Tensor


def _branch_fn(x):
    if x.mean() > 0:
        y = x * 2.0 + 1.0
    else:
        y = -x
    return y


def test_if_both_branches_export():
    with dygraph.guard():
        xpos = dygraph.to_variable(np.ones((2, 3), "f4"))
        xneg = dygraph.to_variable(-np.ones((2, 3), "f4"))
        eager_pos = np.asarray(_branch_fn(xpos)._value)
        eager_neg = np.asarray(_branch_fn(xneg)._value)

        # trace on the POSITIVE input only
        _, tl = djit.TracedLayer.trace(_branch_fn, [xpos])
        ops = [op.type for op in tl.program.global_block.ops]
        assert "cond_pair" in ops, ops
        np.testing.assert_allclose(np.asarray(tl(xpos)[0]._value), eager_pos)
        np.testing.assert_allclose(np.asarray(tl(xneg)[0]._value), eager_neg)


def test_if_return_form():
    def f(x):
        if x.sum() > 0:
            return x + 10.0
        else:
            return x - 10.0

    with dygraph.guard():
        a = dygraph.to_variable(np.full((2,), 1.0, "f4"))
        b = dygraph.to_variable(np.full((2,), -1.0, "f4"))
        _, tl = djit.TracedLayer.trace(f, [a])
        np.testing.assert_allclose(np.asarray(tl(a)[0]._value), [11., 11.])
        np.testing.assert_allclose(np.asarray(tl(b)[0]._value),
                                   [-11., -11.])


def test_while_data_dependent_trip_count():
    def f(x):
        # double until the sum crosses 100: trip count depends on data
        while x.sum() < 100.0:
            x = x * 2.0
        return x

    with dygraph.guard():
        a = dygraph.to_variable(np.full((4,), 1.0, "f4"))   # 5 doublings
        b = dygraph.to_variable(np.full((4,), 30.0, "f4"))  # 1 doubling
        c = dygraph.to_variable(np.full((4,), 99.0, "f4"))  # 0 doublings?
        eager = [np.asarray(f(dygraph.to_variable(
            np.asarray(t._value).copy()))._value) for t in (a, b, c)]
        _, tl = djit.TracedLayer.trace(f, [a])
        ops = [op.type for op in tl.program.global_block.ops]
        assert "while" in ops, ops
        for t, e in zip((a, b, c), eager):
            np.testing.assert_allclose(np.asarray(tl(t)[0]._value), e)


def test_for_range_with_break():
    def f(x):
        acc = x * 0.0
        for i in range(10):
            acc = acc + x
            if acc.sum() > 50.0:
                break
        return acc

    with dygraph.guard():
        small = dygraph.to_variable(np.full((2,), 1.0, "f4"))  # never breaks
        big = dygraph.to_variable(np.full((2,), 30.0, "f4"))   # breaks at 1
        eager_small = np.asarray(f(small)._value)
        eager_big = np.asarray(f(big)._value)
        _, tl = djit.TracedLayer.trace(f, [small])
        np.testing.assert_allclose(np.asarray(tl(small)[0]._value),
                                   eager_small)
        np.testing.assert_allclose(np.asarray(tl(big)[0]._value), eager_big)


def test_bool_ops_and_not():
    def f(x):
        if (x.mean() > 0) and (x.sum() < 10.0):
            y = x + 1.0
        else:
            y = x - 1.0
        if not (x.mean() > 0):
            y = y * 3.0
        return y

    with dygraph.guard():
        ins = [np.full((2,), v, "f4") for v in (1.0, 20.0, -1.0)]
        eager = [np.asarray(f(dygraph.to_variable(v))._value) for v in ins]
        _, tl = djit.TracedLayer.trace(
            f, [dygraph.to_variable(ins[0])])
        for v, e in zip(ins, eager):
            got = tl(dygraph.to_variable(v))[0]
            np.testing.assert_allclose(np.asarray(got._value), e)


def test_jit_save_load_predictor_roundtrip(tmp_path):
    """The criterion: data-dependent branch + loop export via
    jit.save; the loaded Predictor reproduces eager on both branches."""
    from paddle_tpu.hapi.model import InputSpec

    @djit.to_static
    def model(x):
        if x.mean() > 0:
            h = x * 2.0
        else:
            h = x * -3.0
        s = h
        while s.sum() < 64.0:
            s = s * 2.0
        return s

    path = str(tmp_path / "dy2static_model")
    djit.save(model, path,
              input_spec=[Tensor(np.full((2, 2), 0.5, "f4"))])
    loaded = djit.load(path)

    with dygraph.guard():
        for fill in (0.5, -0.25, 5.0):
            x = np.full((2, 2), fill, "f4")
            eager = np.asarray(model._fn(dygraph.to_variable(x))._value)
            got = loaded(dygraph.to_variable(x))
            got = got[0] if isinstance(got, list) else got
            np.testing.assert_allclose(np.asarray(got._value), eager,
                                       rtol=1e-6)


def test_python_control_flow_stays_python():
    """Non-tensor conditions take the plain python path and unroll, as
    the reference's convert shims do."""
    def f(x, n):
        for _ in range(n):
            x = x + 1.0
        if n > 2:
            x = x * 2.0
        return x

    with dygraph.guard():
        x = dygraph.to_variable(np.zeros((2,), "f4"))
        out = f(x, 3)
        np.testing.assert_allclose(np.asarray(out._value), [6.0, 6.0])
        _, tl = djit.TracedLayer.trace(lambda t: f(t, 3), [x])
        np.testing.assert_allclose(np.asarray(tl(x)[0]._value), [6.0, 6.0])


def test_nested_if_converts():
    """Nested ifs must not trip the early-return detector (the inner
    conversion introduces _pt_* defs containing `return`)."""
    def f(x):
        if x.mean() > 0:
            if x.sum() > 10.0:
                y = x * 2.0
            else:
                y = x * 3.0
        else:
            y = -x
        return y

    with dygraph.guard():
        ins = [np.full((2,), v, "f4") for v in (10.0, 1.0, -1.0)]
        eager = [np.asarray(f(dygraph.to_variable(v))._value) for v in ins]
        _, tl = djit.TracedLayer.trace(f, [dygraph.to_variable(ins[0])])
        for v, e in zip(ins, eager):
            np.testing.assert_allclose(
                np.asarray(tl(dygraph.to_variable(v))[0]._value), e)


def test_break_leaves_loop_var_at_breaking_index():
    """Python leaves `i` at the breaking index; the converted loop must
    not run the induction step on the breaking iteration."""
    def g(x):
        k = x * 0.0
        for i in range(10):
            k = k + x
            if k.sum() > 50.0:
                break
        return k + i

    with dygraph.guard():
        big = np.full((2,), 30.0, "f4")
        small = np.full((2,), 1.0, "f4")
        eager_big = np.asarray(g(dygraph.to_variable(big))._value)
        eager_small = np.asarray(g(dygraph.to_variable(small))._value)
        _, tl = djit.TracedLayer.trace(g, [dygraph.to_variable(small)])
        np.testing.assert_allclose(
            np.asarray(tl(dygraph.to_variable(big))[0]._value), eager_big)
        np.testing.assert_allclose(
            np.asarray(tl(dygraph.to_variable(small))[0]._value),
            eager_small)


def test_two_break_sites_nested_guards():
    """A second break firing mid-iteration must skip the statements
    after it (per-region nested guards)."""
    def f(x):
        acc = x * 0.0
        for _ in range(6):
            acc = acc + x
            if acc.sum() > 100.0:
                break
            acc = acc + x
            if acc.sum() > 50.0:
                break
            acc = acc + 1.0
        return acc

    with dygraph.guard():
        ins = [np.full((2,), v, "f4") for v in (1.0, 20.0, 60.0)]
        eager = [np.asarray(f(dygraph.to_variable(v))._value) for v in ins]
        _, tl = djit.TracedLayer.trace(f, [dygraph.to_variable(ins[0])])
        for v, e in zip(ins, eager):
            np.testing.assert_allclose(
                np.asarray(tl(dygraph.to_variable(v))[0]._value), e)


def test_use_prune_keeps_cond_passthrough_producers():
    """Executor.run(use_prune=True) must keep ops producing a cond
    branch's pass-through outputs (regression: _prune_ops dropped them)."""
    def f(x):
        y1 = x * 2.0
        y2 = x * 3.0
        if x.mean() > 0:
            z = y1
        else:
            z = y2
        return z

    with dygraph.guard():
        xv = np.full((2,), 1.0, "f4")
        _, tl = djit.TracedLayer.trace(f, [dygraph.to_variable(xv)])
        exe, scope = tl._ensure_exe()
        out = exe.run(tl.program, feed={tl._feed_names[0]: xv},
                      fetch_list=tl._fetch_names, scope=scope,
                      use_prune=True)
        np.testing.assert_allclose(np.asarray(out[0]), [2.0, 2.0])


def test_early_return_tensor_cond_converts():
    """Round-4 gap (reference return_transformer.py:135): a guard-style
    early return over a TENSOR condition now converts via the return
    flag/value rewrite instead of raising."""
    def f(x):
        if x.mean() > 0:
            return x
        x = x * 2.0
        return x

    with dygraph.guard():
        pos = dygraph.to_variable(np.ones((2,), "f4"))
        neg = dygraph.to_variable(np.full((2,), -1.0, "f4"))
        eager = [np.asarray(f(dygraph.to_variable(
            np.asarray(t._value).copy()))._value) for t in (pos, neg)]
        _, tl = djit.TracedLayer.trace(f, [pos])
        for t, e in zip((pos, neg), eager):
            np.testing.assert_allclose(np.asarray(tl(t)[0]._value), e)


def test_return_inside_while_loop():
    """Return inside a data-dependent while: the return flag folds into
    the loop condition and the value merges through the carry."""
    def f(x):
        while x.sum() < 100.0:
            x = x * 2.0
            if x.mean() > 20.0:
                return x - 1.0
        return x + 0.5

    with dygraph.guard():
        ins = [np.full((4,), v, "f4") for v in (1.0, 30.0, 99.0)]
        eager = [np.asarray(f(dygraph.to_variable(v))._value) for v in ins]
        _, tl = djit.TracedLayer.trace(
            f, [dygraph.to_variable(ins[0])])
        for v, e in zip(ins, eager):
            np.testing.assert_allclose(
                np.asarray(tl(dygraph.to_variable(v))[0]._value), e)


def test_return_inside_for_range_loop():
    def f(x):
        acc = x * 0.0
        for i in range(10):
            acc = acc + x
            if acc.sum() > 50.0:
                return acc * 10.0
        return acc

    with dygraph.guard():
        ins = [np.full((2,), v, "f4") for v in (1.0, 30.0)]
        eager = [np.asarray(f(dygraph.to_variable(v))._value) for v in ins]
        _, tl = djit.TracedLayer.trace(
            f, [dygraph.to_variable(ins[0])])
        for v, e in zip(ins, eager):
            np.testing.assert_allclose(
                np.asarray(tl(dygraph.to_variable(v))[0]._value), e)


def test_statements_after_returning_loop_are_guarded():
    """Code after a loop that may have returned must be skipped when the
    return fired (the not-flag guard cascade)."""
    def f(x):
        for i in range(4):
            x = x + 1.0
            if x.mean() > 3.0:
                return x * 100.0
        x = x - 0.25
        return x

    with dygraph.guard():
        ins = [np.full((2,), v, "f4") for v in (0.0, 5.0)]
        eager = [np.asarray(f(dygraph.to_variable(v))._value) for v in ins]
        _, tl = djit.TracedLayer.trace(
            f, [dygraph.to_variable(ins[0])])
        for v, e in zip(ins, eager):
            np.testing.assert_allclose(
                np.asarray(tl(dygraph.to_variable(v))[0]._value), e)


def test_for_over_tensor_rows_with_list_append():
    """Iterating a tensor yields its rows (ForToWhileTransformer /
    list_transformer roles); appended rows concat back together."""
    from paddle_tpu import tensor as pt_tensor

    def f(x):
        rows = []
        for r in x:
            if r.sum() > 0:
                rows.append(r * 2.0)
            else:
                rows.append(r - 1.0)
        return pt_tensor.stack(rows)

    with dygraph.guard():
        a = np.array([[1.0, 2.0], [-3.0, 1.0], [0.5, -2.0]], "f4")
        eager = np.asarray(f(dygraph.to_variable(a))._value)
        _, tl = djit.TracedLayer.trace(f, [dygraph.to_variable(a)])
        np.testing.assert_allclose(
            np.asarray(tl(dygraph.to_variable(a))[0]._value), eager)


def test_python_guard_early_return_still_traces():
    """`if b is None: return ...` over a PYTHON value is the classic
    forward-signature guard; it must keep tracing (plain python path)."""
    def f(x, b=None):
        if b is None:
            return x * 2.0
        return x + b

    with dygraph.guard():
        x = dygraph.to_variable(np.ones((2,), "f4"))
        _, tl = djit.TracedLayer.trace(lambda t: f(t), [x])
        np.testing.assert_allclose(np.asarray(tl(x)[0]._value), [2.0, 2.0])


def test_layer_forward_hooks_survive_conversion():
    """Trace goes through Layer.__call__, so forward hooks record."""
    from paddle_tpu import nn

    class M(nn.Layer):
        def forward(self, x):
            if x.mean() > 0:
                return x * 2.0
            else:
                return -x

    with dygraph.guard():
        m = M()
        m.register_forward_post_hook(lambda l, i, o: o + 100.0)
        x = dygraph.to_variable(np.ones((2,), "f4"))
        eager = np.asarray(m(x)._value)
        np.testing.assert_allclose(eager, [102.0, 102.0])
        _, tl = djit.TracedLayer.trace(m, [x])
        np.testing.assert_allclose(np.asarray(tl(x)[0]._value), eager)


def test_zero_trip_range_keeps_existing_var():
    def g(x, n):
        k = x * 5.0
        for _ in range(n):
            k = k + 1.0
        return k

    with dygraph.guard():
        x = dygraph.to_variable(np.ones((2,), "f4"))
        # zero-trip range leaves the pre-existing binding untouched
        out = g(x, 0)
        np.testing.assert_allclose(np.asarray(out._value), [5.0, 5.0])
        _, tl = djit.TracedLayer.trace(lambda t: g(t, 0), [x])
        np.testing.assert_allclose(np.asarray(tl(x)[0]._value), [5.0, 5.0])


def test_return_inside_loop_converts():
    """Formerly a loud error; the return rewriter now converts it
    (reference return_transformer.py:135)."""
    def f(x):
        acc = x * 0.0
        for i in range(3):
            acc = acc + x
            if acc.sum() > 1.0:
                return acc
        return acc

    with dygraph.guard():
        ins = [np.full((2,), v, "f4") for v in (1.0, 0.1)]
        eager = [np.asarray(f(dygraph.to_variable(v))._value) for v in ins]
        _, tl = djit.TracedLayer.trace(
            f, [dygraph.to_variable(ins[0])])
        for v, e in zip(ins, eager):
            np.testing.assert_allclose(
                np.asarray(tl(dygraph.to_variable(v))[0]._value), e)


def test_container_for_with_break_stays_python():
    """break under an if inside a python-container loop must not be
    moved into a generated branch function (SyntaxError regression)."""
    def f(x):
        acc = x * 0.0
        for w in [1.0, 2.0, 3.0]:
            acc = acc + x * w
            if float(np.asarray(acc._value).sum()) > 4.0:
                break
        return acc

    with dygraph.guard():
        x = dygraph.to_variable(np.ones((2,), "f4"))
        eager = np.asarray(f(x)._value)
        _, tl = djit.TracedLayer.trace(f, [x])
        np.testing.assert_allclose(np.asarray(tl(x)[0]._value), eager)


def test_container_for_break_still_converts_tensor_ifs():
    """A container loop with a break must STILL convert its tensor-
    conditioned ifs (flag rewrite + real guarded break), so the export
    carries cond ops instead of a baked branch."""
    def f(x):
        acc = x * 0.0
        for w in [1.0, 2.0, 3.0]:
            if acc.mean() > 0.5:
                acc = acc + x * w
            else:
                acc = acc + x * (2.0 * w)
            if float(np.asarray(acc._value).sum()) > 100.0:
                break
        return acc

    with dygraph.guard():
        xs = [np.full((2,), v, "f4") for v in (1.0, -1.0)]
        eager = [np.asarray(f(dygraph.to_variable(v))._value) for v in xs]
        _, tl = djit.TracedLayer.trace(f, [dygraph.to_variable(xs[0])])
        ops = [op.type for op in tl.program.global_block.ops]
        assert "cond_pair" in ops, ops
        for v, e in zip(xs, eager):
            np.testing.assert_allclose(
                np.asarray(tl(dygraph.to_variable(v))[0]._value), e)


def test_static_mode_variable_dispatch():
    """convert shims route framework Variables to layers.cond."""
    from paddle_tpu import layers
    from paddle_tpu.dygraph.dy2static import convert_ifelse
    from paddle_tpu.framework.program import Program, program_guard

    main, startup = Program(), Program()
    with program_guard(main, startup):
        x = layers.data("x", [3])
        pred = layers.reduce_sum(x) > 0.0
        out = convert_ifelse(
            pred, lambda: x * 2.0, lambda: x - 1.0, (), {})
    exe = pt.Executor(pt.CPUPlace())
    o1 = exe.run(main, feed={"x": np.ones((1, 3), "f4")}, fetch_list=[out])
    o2 = exe.run(main, feed={"x": -np.ones((1, 3), "f4")}, fetch_list=[out])
    np.testing.assert_allclose(np.asarray(o1[0]), np.full((1, 3), 2.0))
    np.testing.assert_allclose(np.asarray(o2[0]), np.full((1, 3), -2.0))


def test_return_inside_nested_loop():
    """Return from a while nested in a for: the inner break folds into
    the inner loop condition, the fired-flag guard breaks the outer."""
    def f(x):
        for i in range(3):
            while x.sum() < 50.0:
                x = x * 2.0
                if x.mean() > 8.0:
                    return x + 100.0
            x = x + 1.0
        return x

    with dygraph.guard():
        ins = [np.full((4,), v, "f4") for v in (1.0, 30.0, 60.0)]
        eager = [np.asarray(f(dygraph.to_variable(v))._value) for v in ins]
        _, tl = djit.TracedLayer.trace(f, [dygraph.to_variable(ins[0])])
        for v, e in zip(ins, eager):
            np.testing.assert_allclose(
                np.asarray(tl(dygraph.to_variable(v))[0]._value), e,
                rtol=1e-5)


def test_return_in_both_arms_inside_loop():
    def f(x):
        for i in range(4):
            x = x + 1.0
            if x.mean() > 3.0:
                if x.sum() > 20.0:
                    return x * 10.0
                else:
                    return x * -1.0
        return x

    with dygraph.guard():
        ins = [np.full((4,), v, "f4") for v in (0.0, 3.0, 9.0)]
        eager = [np.asarray(f(dygraph.to_variable(v))._value) for v in ins]
        _, tl = djit.TracedLayer.trace(f, [dygraph.to_variable(ins[0])])
        for v, e in zip(ins, eager):
            np.testing.assert_allclose(
                np.asarray(tl(dygraph.to_variable(v))[0]._value), e,
                rtol=1e-5)
