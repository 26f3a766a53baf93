"""Pins that the four cells read as they did before the harness took
token inputs and shared weights: at seed 0 and a small size on the
CPU, a digest of the traffic's arrays; the client model configuration
that `program_parts` hands the program; and the client-model FLOPs of
a period at the cell's own size. The digests and counts were taken
with the harness as it stood before that change. The pins hold these
four cells alone: a cell added later is not pinned, and a field that
the program's ClientModelConfig gains with a default leaves them
standing."""
import dataclasses
import hashlib

import numpy as np
import pytest

from benchkit import (CELLS, CPU_FLOPS_CAP, TINY, harness,  # noqa: F401
                      tiny_cell)

import traffic

# each cell's own clients, 24 / 8 / 8 rows
ROWS = {"train_rows": 24, "test_rows": 8, "ref_rows": 8}
TCN = {"name": "tcn3-w32-aecg", "kind": "tcn", "input_shape": (60, 1),
       "num_classes": 2, "hidden": (32, 32, 32), "kernel_size": 5,
       "citation": "", "arch": ()}
CNN = {"name": "conv2-fc128-mnist", "kind": "cnn",
       "input_shape": (28, 28, 1), "num_classes": 10, "hidden": (32, 64),
       "kernel_size": 3, "citation": "", "arch": (("fc_width", 128),)}
M10 = "f174f2855548a9eb30d0424faceec819c6bf2dbf1c6f00c15dcf41857a0e8b73"
PINS = {
    "aecg-public-m1024-sync": (
        "c25261ad308448a013d69626344cf75dedb883d2a56f7dc90877ccca5875943c",
        TCN, 2097288314880),
    "mnist-personal-m128-sync": (
        "6feba8506f4ea3b07811e5a7f179761ed7cf51abcdce2e61234ad4c2e6f6f725",
        CNN, 937920757760),
    "mnist-personal-m10-sync": (M10, CNN, 68376448000),
    "mnist-service-m10-g2": (M10, CNN, 136752896000),
}
TRAFFIC_KEYS = {"x_train", "y_train", "x_test", "y_test", "x_ref", "y_ref"}


def digest(data) -> str:
    h = hashlib.sha256()
    for k in sorted(data):
        a = np.asarray(data[k])
        h.update(f"{k}:{a.dtype.str}:{a.shape};".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def check_pinned(cells):
    """The four pinned cells are among `cells`; any other cell there
    is left unpinned."""
    assert set(PINS) <= set(cells) and len(PINS) == 4


def check_model_config(mcfg, pin):
    """The pinned fields as pinned, and every other field of the
    configuration's class at the class's default."""
    fields = {f.name: f for f in dataclasses.fields(mcfg)}
    assert set(pin) <= set(fields)
    for name, f in fields.items():
        if name in pin:
            want = pin[name]
        elif f.default_factory is not dataclasses.MISSING:
            want = f.default_factory()
        else:
            want = f.default
        assert getattr(mcfg, name) == want, name


def check_config_class(cls, base):
    """`cls` has `arch`, empty by default, and is either `base` itself
    or a subclass of it that adds `arch` alone."""
    arch = {f.name: f for f in dataclasses.fields(cls)}.get("arch")
    assert arch is not None and arch.default == ()
    if cls is not base:
        added = {f.name for f in dataclasses.fields(cls)} - {
            f.name for f in dataclasses.fields(base)}
        assert issubclass(cls, base) and added == {"arch"}


def test_every_cell_is_pinned():
    """Each of the four pinned cells is still in BENCHMARK.json."""
    check_pinned(CELLS)


@pytest.mark.parametrize("name", PINS)
def test_traffic_is_bit_identical(harness, name):
    cell = harness.load_cell(name)
    data = traffic.generate(cell["cfg"], dict(cell["wl"], **ROWS), 0)
    assert digest(data) == PINS[name][0]


@pytest.mark.parametrize("name", PINS)
def test_program_gets_the_same_model_and_data(harness, name):
    from repro.configs.paper_models import ClientModelConfig
    cell = tiny_cell(harness, name)
    apply_fn, _, _, data, _ = harness.program_parts(cell, 0)
    mcfg = apply_fn.args[0]
    assert isinstance(mcfg, ClientModelConfig)
    check_model_config(mcfg, PINS[name][1])
    assert set(data) == TRAFFIC_KEYS


@pytest.mark.parametrize("name", PINS)
def test_period_flops_are_unchanged(harness, name):
    cell = harness.load_cell(name)
    m = cell["wl"]["clients"]
    n = min(cell["cfg"]["fed"]["num_neighbors"], m - 1)
    assert harness.period_flops(cell, n) == PINS[name][2]


def test_cpu_cap_is_four_times_the_largest_pinned_cell(harness):
    """The pinned cells run on the CPU at TINY as they are, the largest
    at a quarter of the cap."""
    flops = []
    for name in PINS:
        cell = harness.load_cell(name)
        assert "cpu" not in cell["cfg"]
        cell["wl"] = dict(cell["wl"], **TINY)
        n = min(cell["cfg"]["fed"]["num_neighbors"], TINY["clients"] - 1)
        flops.append(harness.period_flops(cell, n))
    assert CPU_FLOPS_CAP == 4 * max(flops)


def test_the_config_class_adds_only_arch(harness, monkeypatch):
    """Until the program's ClientModelConfig has an `arch` field the
    harness subclasses it with that field alone, empty by default;
    once it has one, the harness takes the program's class as it is."""
    from repro.configs import paper_models
    base = paper_models.ClientModelConfig
    cls = harness._config_class()
    check_config_class(cls, base)
    assert cls(name="x", kind="mlp", input_shape=(1,),
               num_classes=2).arch == ()

    @dataclasses.dataclass(frozen=True)
    class WithArch(base):
        arch: tuple = ()
    monkeypatch.setattr(paper_models, "ClientModelConfig", WithArch)
    harness._config_class.cache_clear()
    try:
        assert harness._config_class() is WithArch
    finally:
        harness._config_class.cache_clear()


def _grown(base, fields):
    return dataclasses.make_dataclass("ClientModelConfig", fields,
                                      bases=(base,), frozen=True)


GROWN = {
    "a_field_with_a_default": [("rope_theta", float,
                                dataclasses.field(default=1e4))],
    "arch": [("arch", tuple, dataclasses.field(default=()))],
    "arch_and_a_field": [("arch", tuple, dataclasses.field(default=())),
                         ("experts_held", tuple,
                          dataclasses.field(default=()))],
}


@pytest.mark.parametrize("grown", GROWN)
def test_pins_hold_when_the_program_class_grows(harness, monkeypatch,
                                                 grown):
    """The program's ClientModelConfig with a field added, by default
    empty or neutral: the pinned cells' configurations still meet
    their pins, and the harness's class still adds `arch` alone or is
    the program's own."""
    from repro.configs import paper_models
    cls = _grown(paper_models.ClientModelConfig, GROWN[grown])
    monkeypatch.setattr(paper_models, "ClientModelConfig", cls)
    harness._config_class.cache_clear()
    try:
        check_config_class(harness._config_class(), cls)
        for name in PINS:
            mcfg = harness.client_model_config(harness.load_cell(name)["cfg"])
            assert isinstance(mcfg, cls)
            check_model_config(mcfg, PINS[name][1])
    finally:
        harness._config_class.cache_clear()
