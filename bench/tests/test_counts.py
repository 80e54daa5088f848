"""Each ``bench/counts/<program>.json`` names exactly the boundary fields
that today's ``build_*_program`` declares at each configuration's shapes,
and says rightly which of them a call must read and write.  A program
change that fails this needs a benchmark change to the counts."""

import pytest

import spec
import system


def _programs(cfg):
    from repro.fv3.dyncore import _build_programs

    traffic = spec.traffic("nsplit6")
    fcfg = system.fv3_config(cfg, traffic)
    return _build_programs(fcfg, fcfg.seq_dom())


def _configs():
    bench = spec.benchmark()
    return [spec.config(bench, c["name"]) for c in bench["configs"]]


@pytest.mark.parametrize("cfg", _configs(), ids=lambda c: c["name"])
def test_counts_name_the_declared_boundary_fields(cfg):
    counts = spec.counts()
    for prog in _programs(cfg):
        fields = counts[prog.name]["fields"]
        boundary = {n: d for n, d in prog.fields.items() if not d.transient}
        assert set(fields) == set(boundary), prog.name
        nodes = prog.all_nodes()
        for name, decl in boundary.items():
            c = fields[name]
            assert c.get("interface", False) == decl.interface, name
            assert c["write"] == any(name in n.writes() for n in nodes), name
            first = next((n for n in nodes
                          if name in n.reads() or name in n.writes()), None)
            is_input = first is not None and name in first.reads()
            readers = [n for n in nodes if name in n.reads()]
            dead = all(prog.fields[w].transient
                       and not any(w in m.reads() for m in nodes)
                       for n in readers for w in n.writes())
            assert c.get("input", c["read"]) == is_input, (prog.name, name)
            assert c["read"] == (is_input and not dead), (prog.name, name)


def test_step_structure_names_counted_programs():
    counts = spec.counts()
    for part in ("acoustic_substep", "remap_iteration"):
        for p in counts["step"][part]["programs"]:
            assert "fields" in counts[p]
