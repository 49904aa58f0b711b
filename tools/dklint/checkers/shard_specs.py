"""DK123 — shard_map partition-spec soundness, judged off-device.

Every judgement is grounded in :mod:`tools.dklint.shapes`' abstract
evaluation of the call site: the governing mesh (``make_mesh`` /
``make_mesh_grid`` / raw ``Mesh``), the ``in_specs``/``out_specs``
PartitionSpecs, and — when the mapped function is invoked in the same
scope — the operand shapes.  Flags only what is *provable*:

  * a spec naming an axis the governing mesh does not declare;
  * the same mesh axis used twice within one spec (jax rejects this at
    trace time);
  * a spec whose rank exceeds the operand's known rank, and an explicit
    ``in_specs`` tuple whose length disagrees with the operand count;
  * a mesh-axis size that provably fails to divide the concrete dim it
    partitions.

Calls through ``compat.shard_map`` get the same judgement as direct
``jax.shard_map`` calls.  Partial-manual maps (``axis_names`` a strict
subset of the mesh axes) are valid and not reported.

Anything unresolvable is trusted, the DK104/DK108 stance.
"""

from __future__ import annotations

import ast
from typing import Iterable, List, Optional

from tools.dklint import shapes
from tools.dklint.core import Checker, FileInfo, Finding, Project
from tools.dklint.registry import register
from tools.dklint.shapes import (
    UNKNOWN, ArrayVal, Evaluator, MeshVal, SpecVal, provably_not_divides,
)


def _spec_list(value) -> Optional[List[object]]:
    """Normalize an ``in_specs``/``out_specs`` value into a list of per-leaf
    entries (SpecVal or UNKNOWN).  A single spec is a valid pytree prefix
    (applied to every operand); None means the structure itself is
    unresolvable."""
    if isinstance(value, SpecVal):
        return [value]
    if isinstance(value, tuple):
        return [v if isinstance(v, SpecVal) else UNKNOWN for v in value]
    return None


@register
class ShardSpecChecker(Checker):
    rule = "DK123"
    name = "shard-map-spec-soundness"
    description = (
        "shard_map in_specs/out_specs provably unsound: axis absent from "
        "the governing mesh, duplicate axis in one spec, rank exceeding "
        "the operand's, or a non-dividing mesh axis"
    )

    def collect(self, project: Project, fi: FileInfo) -> None:
        shapes.collect_facts(project, fi)

    def check(self, project: Project, fi: FileInfo) -> Iterable[Finding]:
        for site in shapes.shard_map_sites(project, fi):
            yield from self._check_site(project, fi, site)

    # ------------------------------------------------------------------ site

    def _check_site(self, project: Project, fi: FileInfo,
                    site: shapes.ShardMapSite) -> Iterable[Finding]:
        call = site.call
        mesh = site.mesh if isinstance(site.mesh, MeshVal) else None
        in_specs = _spec_list(site.in_specs)
        out_specs = _spec_list(site.out_specs)

        for which, specs in (("in_specs", in_specs), ("out_specs", out_specs)):
            if specs is None:
                continue
            for i, spec in enumerate(specs):
                if not isinstance(spec, SpecVal):
                    continue
                yield from self._check_spec(fi, call, mesh, which, i, spec,
                                            len(specs))

        # operand-grounded checks need the invocation
        if site.invoke is not None and in_specs is not None:
            yield from self._check_operands(project, fi, site, in_specs)

    def _check_spec(self, fi: FileInfo, call: ast.Call,
                    mesh: Optional[MeshVal], which: str, index: int,
                    spec: SpecVal, total: int) -> Iterable[Finding]:
        where = which if total == 1 else f"{which}[{index}]"
        seen = set()
        for entry in spec.entries:
            if entry is UNKNOWN:
                continue
            for axis in entry:
                if axis in seen:
                    yield Finding(
                        path=fi.relpath, line=call.lineno,
                        col=call.col_offset, rule=self.rule,
                        message=(
                            f"{where} uses mesh axis '{axis}' more than "
                            "once in a single PartitionSpec"
                        ),
                    )
                seen.add(axis)
                if mesh is not None and axis not in mesh.names:
                    yield Finding(
                        path=fi.relpath, line=call.lineno,
                        col=call.col_offset, rule=self.rule,
                        message=(
                            f"{where} names axis '{axis}', absent from the "
                            "governing mesh (axes: "
                            f"{', '.join(mesh.names) or 'none'})"
                        ),
                    )

    def _check_operands(self, project: Project, fi: FileInfo,
                        site: shapes.ShardMapSite,
                        in_specs: List[object]) -> Iterable[Finding]:
        invoke = site.invoke
        if any(isinstance(a, ast.Starred) for a in invoke.args) or \
                invoke.keywords:
            return
        operands = list(invoke.args)
        explicit_tuple = isinstance(site.in_specs, tuple)
        if explicit_tuple and len(in_specs) != len(operands):
            yield Finding(
                path=fi.relpath, line=invoke.lineno,
                col=invoke.col_offset, rule=self.rule,
                message=(
                    f"shard_map in_specs has {len(in_specs)} entries but "
                    f"the mapped function is invoked with {len(operands)} "
                    "operands"
                ),
            )
            return
        facts = shapes._facts_for(project, fi)
        encl = facts.encl.get(id(invoke))
        ev = Evaluator(project, fi, encl)
        mesh = site.mesh if isinstance(site.mesh, MeshVal) else None
        for i, operand in enumerate(operands):
            spec = in_specs[i] if explicit_tuple else in_specs[0]
            if not isinstance(spec, SpecVal):
                continue
            got = ev.eval(operand)
            if not isinstance(got, ArrayVal) or got.shape is None:
                continue
            if spec.rank > len(got.shape):
                yield Finding(
                    path=fi.relpath, line=invoke.lineno,
                    col=invoke.col_offset, rule=self.rule,
                    message=(
                        f"in_specs[{i}] {spec!r} has rank {spec.rank} but "
                        f"operand {i} has rank {len(got.shape)} "
                        f"(shape {got!r})"
                    ),
                )
                continue
            if mesh is None:
                continue
            for d, entry in zip(got.shape, spec.entries):
                if entry is UNKNOWN or d is None:
                    continue
                factor = 1
                for axis in entry:
                    size = mesh.size_of(axis)
                    if size is None:
                        factor = 0
                        break
                    factor *= size
                if factor > 1 and provably_not_divides(factor, d):
                    yield Finding(
                        path=fi.relpath, line=invoke.lineno,
                        col=invoke.col_offset, rule=self.rule,
                        message=(
                            f"mesh axes {list(entry)} (total size {factor}) "
                            f"provably do not divide dim {d!r} of operand "
                            f"{i} (in_specs[{i}] {spec!r})"
                        ),
                    )
