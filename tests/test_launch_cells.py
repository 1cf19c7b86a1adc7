"""Launch-layer tests: every cell builder lowers+compiles at reduced scale
on a 1×1 host mesh (the full-scale 256/512-chip compiles are the dry-run
sweep; this is the fast regression guard)."""
import jax
import pytest

from repro.configs import all_cells, get_arch
from repro.launch.mesh import make_host_mesh, resolve_in_shardings
from repro.launch.steps import build_cell

# one representative shape per family kind keeps this under a minute
FAST_CELLS = [
    ("qwen2-1.5b", "train_4k"),
    ("gemma2-9b", "decode_32k"),
    ("olmoe-1b-7b", "prefill_32k"),
    ("gcn-cora", "full_graph_sm"),
    ("gatedgcn", "molecule"),
    ("nequip", "minibatch_lg"),
    ("meshgraphnet", "ogb_products"),
    ("dlrm-mlperf", "train_batch"),
    ("dlrm-mlperf", "serve_p99"),
    ("dlrm-mlperf", "retrieval_cand"),
]


@pytest.fixture(scope="module", autouse=True)
def host_mesh():
    # the mesh is this module's alone: leaving it set would hand a CPU mesh
    # to whichever test runs next in the process (a TPU compile among them)
    mesh = make_host_mesh()
    with jax.set_mesh(mesh):
        yield mesh


@pytest.mark.parametrize("arch,shape", FAST_CELLS)
def test_cell_lowers_and_compiles_reduced(arch, shape, host_mesh):
    cell = build_cell(arch, shape, reduced=True)
    jitted = jax.jit(cell.fn, in_shardings=resolve_in_shardings(host_mesh, cell.in_specs),
                     donate_argnums=cell.donate_argnums)
    compiled = jitted.lower(*cell.args).compile()
    assert compiled.cost_analysis() is not None
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes >= 0


def test_all_cells_enumerate_40():
    cells = all_cells()
    assert len(cells) == 40
    archs = {a for a, _ in cells}
    assert len(archs) == 10


def test_registry_configs_buildable():
    for arch_id in {a for a, _ in all_cells()}:
        spec = get_arch(arch_id)
        cfg = spec.config()
        red = spec.reduced()
        assert cfg.name and red.name
