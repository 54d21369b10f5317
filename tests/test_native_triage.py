"""Native loader triage (native/__init__.py + tools/check_native.py):
the GLIBCXX required-vs-provided diagnosis and its bounded /metrics
reason. These run with or without a loadable library — the triage is
exactly for the hosts where it does NOT load."""

from __future__ import annotations

import os
import sys

import pytest

from log_parser_tpu import native
from log_parser_tpu.obs import native_load_reason


def test_glibcxx_versions_reads_symbol_tags(tmp_path):
    blob = tmp_path / "fake.so"
    blob.write_bytes(
        b"\x00GLIBCXX_3.4\x00junk\x00GLIBCXX_3.4.29\x00GLIBCXX_3.4.21\x00"
        b"GLIBCXX_3.4\x00not-a-tag GLIBCX_9.9\x00"
    )
    got = native._glibcxx_versions(blob)
    assert got == [(3, 4), (3, 4, 21), (3, 4, 29)]
    assert native._glibcxx_versions(tmp_path / "absent.so") == []


def test_triage_names_the_gap(tmp_path, monkeypatch):
    so = tmp_path / "scanner.so"
    so.write_bytes(b"\x00GLIBCXX_3.4\x00GLIBCXX_3.4.99\x00")
    host = tmp_path / "libstdc++.so.6"
    host.write_bytes(b"\x00GLIBCXX_3.4\x00GLIBCXX_3.4.28\x00")
    monkeypatch.setattr(native, "find_libstdcxx", lambda: str(host))
    tri = native.glibcxx_triage(so)
    assert tri["required"] == ["GLIBCXX_3.4", "GLIBCXX_3.4.99"]
    assert tri["provided"] == ["GLIBCXX_3.4", "GLIBCXX_3.4.28"]
    # only versions NEWER than everything the host exports are the gap
    assert tri["missing"] == ["GLIBCXX_3.4.99"]
    assert tri["libstdcxx"] == str(host)


def test_find_libstdcxx_points_at_a_real_file():
    path = native.find_libstdcxx()
    # every host this suite runs on links C++ somewhere (JAX does)
    assert path is not None and os.path.exists(path)
    assert "libstdc++" in os.path.basename(path)


def test_reason_vocabulary_maps_glibcxx_mismatch():
    err = ("glibcxx mismatch: needs GLIBCXX_3.4.29; host libstdc++ tops "
           "out at GLIBCXX_3.4.28 — rebuild on this host")
    doc = {"available": False, "loadError": err}
    assert native_load_reason(doc) == "glibcxx_mismatch"
    assert native_load_reason({"available": True}) == "ok"
    assert native_load_reason(
        {"available": False, "loadError": "load failed: boom"}
    ) == "load_failed"


def test_check_native_tool_reports_without_booting():
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
    try:
        import check_native
    finally:
        sys.path.pop(0)
    doc = check_native.triage()
    assert doc["source_exists"] is True
    assert isinstance(doc["glibcxx"]["required"], list)
    # the tool's verdict agrees with the runtime loader's
    assert doc["loaded"] == native.available()
    if not doc["loaded"]:
        assert doc["load_error"]


class TestBuildRule:
    """The shared object is rebuilt unless its stamp holds the sha256 of
    the committed source — content, not mtime, so a fresh copy of the
    tree builds from what it carries."""

    @pytest.fixture
    def tree(self, monkeypatch, tmp_path):
        src = tmp_path / "native" / "log_parser_native.cpp"
        src.parent.mkdir()
        src.write_text("// v1\n")
        so = tmp_path / "native" / "build" / "log_parser_native.so"
        monkeypatch.setattr(native, "_SRC", src)
        monkeypatch.setattr(native, "_SO", so)
        monkeypatch.setattr(native, "_STAMP", so.with_name(so.name + ".sha256"))
        builds: list[str] = []

        def fake_compile(digest):
            so.write_bytes(b"ELF")
            native._STAMP.write_text(digest + "\n")
            builds.append(digest)
            return True

        monkeypatch.setattr(native, "_compile", fake_compile)
        return src, so, builds

    def test_builds_when_absent_then_reuses(self, tree):
        src, so, builds = tree
        assert native._ensure_built()
        assert builds == [native._src_digest()]
        assert native._ensure_built()
        assert len(builds) == 1  # stamp current: no second build

    def test_source_change_rebuilds_whatever_the_mtimes(self, tree):
        src, so, builds = tree
        native._ensure_built()
        src.write_text("// v2\n")
        # a copied tree can leave the binary NEWER than the source
        os.utime(so, (4e9, 4e9))
        assert native._ensure_built()
        assert len(builds) == 2 and builds[1] == native._src_digest()

    def test_binary_without_stamp_is_rebuilt(self, tree):
        src, so, builds = tree
        so.parent.mkdir(parents=True)
        so.write_bytes(b"ELF from somewhere else")
        assert native._ensure_built()
        assert len(builds) == 1

    def test_real_build_stamps_the_source_digest(self, monkeypatch, tmp_path):
        """The real g++ path writes the .so and its stamp atomically."""
        import shutil

        if shutil.which("g++") is None:
            pytest.skip("no g++ on this host")
        so = tmp_path / "build" / "log_parser_native.so"
        so.parent.mkdir()
        monkeypatch.setattr(native, "_SO", so)
        monkeypatch.setattr(native, "_STAMP", so.with_name(so.name + ".sha256"))
        digest = native._src_digest()
        assert native._compile(digest)
        assert so.stat().st_size > 0
        assert native._stamp() == digest
        assert not list(so.parent.glob("*.tmp"))


def test_build_product_is_not_committed():
    import subprocess

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        ["git", "ls-files", "native/build"], capture_output=True, text=True,
        cwd=repo,
    )
    if r.returncode != 0:
        pytest.skip("not a git checkout")
    assert r.stdout.strip() == ""
