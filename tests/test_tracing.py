"""Named scopes on the ops a compiled program emits: `<NodeType>:<dest>`
per plan node, `SeqLoop` per loop, the group-by's stages below its node,
and `layout` around the Pallas segment kernel's pads and transposes.  Read
from the `op_name` metadata of the compiled whole program, which a TPU
profile reports per device op (`tf_op`)."""
import re

import jax
import pytest

from repro.core import compile_program, programs
from repro.core.lower import ExecContext

from test_core_programs import data_for


def op_names(cp, inputs) -> list:
    """The `op_name` of every instruction of the compiled whole program
    (fused computations included)."""
    env = cp.prepare_env(inputs)
    static = {k: v for k, v in env.items() if isinstance(v, int)}
    arrays = {k: v for k, v in env.items() if k not in static}

    def whole(arrays):
        e = dict(static, **arrays)
        cp.executor.execute(cp.plan, e, ExecContext())
        return {n: e[n] for n in cp.program.outputs}
    text = jax.jit(whole).lower(arrays).compile().as_text()
    return re.findall(r'op_name="([^"]*)"', text)


def scopes(cp, inputs) -> set:
    """Each op's scope path: the part between the outer jit and the
    primitive, nested jits left out."""
    out = set()
    for name in op_names(cp, inputs):
        parts = [p for p in name.split("/")[1:-1]
                 if not p.startswith("jit(")]
        out.add("/".join(parts))
    return out


def group_by(tmp_path, **kw):
    cp = compile_program(programs.group_by,
                         autotune_cache=str(tmp_path / "none.json"), **kw)
    return cp, data_for("group_by")


# group_by's value is the bag's column itself: the value stage emits no op
@pytest.mark.parametrize("salting,stages", [
    ("off", {"keys", "sort", "reduce", "combine"}),
    ("force:4", {"keys", "sort", "reduce", "salt_fold", "combine"}),
])
def test_sort_backend_stages(tmp_path, salting, stages):
    cp, ins = group_by(tmp_path, op_select="force:sort",
                       skew_salting=salting)
    found = scopes(cp, ins)
    assert {f"SegmentReduce:C/{s}" for s in stages} <= found
    # the sort carries the values: no permutation gathers after it
    assert not any(s.startswith("SegmentReduce:C/permute") for s in found)
    # every group-by op sits under its node; no stage is left unnamed
    assert all(s.startswith("SegmentReduce:C/") for s in found if s)


def test_pallas_layout_scope(tmp_path):
    cp, ins = group_by(tmp_path, op_select="force:pallas",
                       skew_salting="off")
    found = scopes(cp, ins)
    assert "SegmentReduce:C/reduce/layout" in found
    assert "SegmentReduce:C/sort" not in found


def test_loop_and_node_scopes(tmp_path):
    cp = compile_program(programs.pagerank,
                         autotune_cache=str(tmp_path / "none.json"))
    found = scopes(cp, data_for("pagerank"))
    loop = [s for s in found if s.startswith("SeqLoop/")]
    assert loop, found
    # the rank gather P[s] / C[s] is the group-by's value stage
    assert "SeqLoop/while/body/SegmentReduce:NP/value" in loop
    nodes = {m.group(0) for s in found
             for m in re.finditer(r"[A-Z]\w*:[^/]+", s)}
    assert all(re.fullmatch(r"[A-Z]\w*:[\w+]+", n) for n in nodes), nodes



def test_cached_executables_carry_their_own_scopes(tmp_path, monkeypatch):
    """With the persistent cache on, a program whose scopes differ does not
    load another's executable (its profile would show the other's scope
    names); the same program loads its own."""
    import jax.numpy as jnp
    from jax._src import monitoring
    from jax.experimental.compilation_cache import compilation_cache
    from repro import compile_cache
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes",
             "jax_compilation_cache_include_metadata_in_key",
             "jax_hlo_source_file_canonicalization_regex")
    before = {n: getattr(jax.config, n) for n in names}
    hits = []

    def listen(event, **kw):
        if event.startswith("/jax/compilation_cache/cache_"):
            hits.append(event.endswith("hits"))

    def compile_scoped(scope):
        def f(x):
            with jax.named_scope(scope):
                return x * 2.0 + 1.0
        jax.jit(f).lower(jnp.ones(8)).compile()
        return hits.pop()

    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    monitoring.register_event_listener(listen)
    try:
        assert compile_cache.enable() == str(tmp_path)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        compilation_cache.reset_cache()
        # one call site: the call stack is part of the metadata too
        assert [compile_scoped(s) for s in ("a", "b", "a")] \
            == [False, False, True]
    finally:
        monitoring.unregister_event_listener(listen)
        for n, v in before.items():
            jax.config.update(n, v)
        compilation_cache.reset_cache()
