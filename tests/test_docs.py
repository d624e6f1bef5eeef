"""Docs-consistency gates: the registry-rendered API reference and the
doc tree's cross-links can never silently drift from the code.

``docs/API.md`` embeds a matrix generated FROM the dispatch registry
(``ff.render_api_table``); these tests fail when a newly registered op or
implementation is missing from the document, or the committed matrix is
stale (fix: ``python -m repro.ff.docgen --write docs/API.md``).  The
NUMERICS.md error-contract table is enforced separately — its snippets run
as doctests (``--doctest-glob=NUMERICS.md`` in pyproject).
"""
import os

import repro.ff as ff
from repro.ff import docgen, dispatch
from repro.launch.mesh import make_mesh

ROOT = os.path.join(os.path.dirname(__file__), "..")
API = os.path.join(ROOT, "docs", "API.md")


def test_api_doc_in_sync_with_registry():
    problems = docgen.check_doc(API)
    assert not problems, "\n".join(problems)


def test_api_matrix_lists_every_impl():
    table = ff.render_api_table()
    for op in dispatch.ops():
        assert f"`ff.{op}`" in table, op
        for impl in dispatch.impls(op):
            assert f"`{impl}`" in table, (op, impl)


def test_api_matrix_is_static_markdown():
    """The matrix must be machine-independent (registration data only):
    rendering twice — and under a different ambient scope — is identical."""
    import jax

    t1 = ff.render_api_table()
    mesh = make_mesh((1, 1), ("data", "model"))
    with ff.on_mesh(mesh, axis="data"), ff.use(matmul="dot2"):
        t2 = ff.render_api_table()
    assert t1 == t2
    assert t1.startswith(docgen.BEGIN) and t1.endswith(docgen.END)


def test_every_op_has_numerics_or_api_contract():
    """Each registered op appears in the NUMERICS contract tables or (for
    composites whose contract is the cross-impl ulp pin) is named there."""
    with open(os.path.join(ROOT, "docs", "NUMERICS.md")) as f:
        numerics = f.read()
    for op in dispatch.ops():
        if op == "adamw_update":
            # optimizer chain: contract = bitwise jnp/fused equivalence,
            # documented in DESIGN_fusion.md and pinned by test_fusion
            continue
        assert f"ff.{op}" in numerics, f"ff.{op} missing from NUMERICS.md"


def test_readme_links_docs_tier():
    with open(os.path.join(ROOT, "README.md")) as f:
        readme = f.read()
    for doc in ("docs/API.md", "docs/NUMERICS.md", "docs/VERIFY.md",
                "docs/DESIGN_ozaki.md", "docs/DESIGN_fusion.md",
                "docs/DESIGN_sharded.md", "docs/DESIGN_math.md",
                "docs/DESIGN_robustness.md",
                "docs/DESIGN_observability.md"):
        assert doc in readme, f"README does not link {doc}"
        assert os.path.exists(os.path.join(ROOT, doc)), doc


def test_verify_doc_in_sync_with_contract_registry():
    """docs/VERIFY.md embeds the rendered contracts table between marker
    comments; a registry edit without a doc regen fails here."""
    from repro.verify import contracts

    with open(os.path.join(ROOT, "docs", "VERIFY.md")) as f:
        ok, msg = contracts.check_doc(f.read())
    assert ok, msg


def test_numerics_proof_status_column():
    """Every NUMERICS.md contract row named in NUMERICS_STATUS carries
    exactly the registry's proof status in its table row."""
    from repro.verify import contracts

    with open(os.path.join(ROOT, "docs", "NUMERICS.md")) as f:
        lines = f.read().splitlines()
    for token, status in contracts.NUMERICS_STATUS.items():
        rows = [ln for ln in lines
                if ln.startswith("|") and token + " " in ln]
        assert rows, f"NUMERICS.md has no table row for {token}"
        for ln in rows:
            assert f"**{status}**" in ln, (token, status, ln)
        others = {f"**{s}**" for s in contracts.STATUSES} - {f"**{status}**"}
        for ln in rows:
            assert not any(o in ln for o in others), (token, ln)


def test_readme_verified_contracts_section():
    with open(os.path.join(ROOT, "README.md")) as f:
        readme = f.read()
    assert "## Verified contracts" in readme
    assert "repro.verify" in readme
