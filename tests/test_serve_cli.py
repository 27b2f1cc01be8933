"""The serving CLI end to end: ``launch.serve.main`` driven through
``sys.argv`` on each cluster backend, one gateway loop for all."""
import re
import sys

import pytest

from repro.launch import serve


@pytest.mark.parametrize("argv,n", [
    (["--backend", "py", "--policy", "mixing", "--requests", "40"], 40),
    (["--backend", "vec", "--policy", "jsq", "--requests", "40"], 40),
    (["--backend", "py", "--policy", "mixing", "--chaos-seed", "7",
      "--chaos-crashes", "1", "--failover", "--requests", "60"], 60),
    (["--backend", "engine", "--reduced", "--policy", "mixing",
      "--instances", "2", "--requests", "8"], 8),
], ids=["py-mixing", "vec-jsq", "py-chaos-failover", "engine-reduced"])
def test_serve_cli_runs_through_gateway(argv, n, monkeypatch, tmp_path,
                                        capsys):
    # a set cache dir makes use_compile_cache() leave JAX's config alone
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(sys, "argv", ["serve", *argv])
    serve.main()
    out = capsys.readouterr().out
    policy = argv[argv.index("--policy") + 1]
    assert f"policy={policy} served n={n} admitted={n} shed=0" in out
    if "--chaos-seed" in argv:
        # the scheduled crash fired and failover re-admitted its orphans
        orphaned, retried = map(int, re.search(
            r"chaos: orphaned=(\d+) retried=(\d+)", out).groups())
        assert orphaned > 0 and retried == orphaned
