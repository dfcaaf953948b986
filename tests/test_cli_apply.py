"""CLI + applier end-to-end over the examples corpus."""

import os
import textwrap

from open_simulator_tpu.cli.main import main

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_version(capsys):
    assert main(["version"]) == 0
    assert "simon-tpu version" in capsys.readouterr().out


def test_apply_demo_fits(capsys):
    rc = main(["apply", "-f", os.path.join(REPO, "examples/config.yaml"), "--max-new-nodes", "4"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "no new nodes needed" in out
    assert "=== Cluster ===" in out
    assert "orders-db-2" in out


def test_apply_needs_new_nodes(tmp_path, capsys):
    # Undersized cluster: one small worker; app wants 4 big pods.
    cluster = tmp_path / "cluster"
    cluster.mkdir()
    (cluster / "node.yaml").write_text(textwrap.dedent("""
        apiVersion: v1
        kind: Node
        metadata: {name: tiny-0}
        status:
          allocatable: {cpu: "2", memory: 4Gi, pods: "110"}
    """))
    apps = tmp_path / "apps"
    apps.mkdir()
    (apps / "big.yaml").write_text(textwrap.dedent("""
        apiVersion: apps/v1
        kind: Deployment
        metadata: {name: big, namespace: default}
        spec:
          replicas: 4
          selector: {matchLabels: {app: big}}
          template:
            metadata: {labels: {app: big}}
            spec:
              containers:
                - name: c
                  image: registry.local/big:1
                  resources: {requests: {cpu: 1500m, memory: 2Gi}}
    """))
    (tmp_path / "newnode.yaml").write_text(textwrap.dedent("""
        apiVersion: v1
        kind: Node
        metadata: {name: template}
        status:
          allocatable: {cpu: "4", memory: 8Gi, pods: "110"}
    """))
    (tmp_path / "config.yaml").write_text(textwrap.dedent("""
        apiVersion: simon/v1alpha1
        kind: Config
        metadata: {name: t}
        spec:
          cluster: {customConfig: cluster}
          appList:
            - {name: big, path: apps}
          newNode: newnode.yaml
    """))
    rc = main(["apply", "-f", str(tmp_path / "config.yaml"), "--max-new-nodes", "8"])
    out = capsys.readouterr().out
    assert rc == 0
    # 4 pods x 1500m: tiny-0 fits 1 (2000m); each new 4-cpu node fits 2.
    # 3 remaining pods -> 2 new nodes.
    assert "requires 2 new node(s)" in out
    assert "(new)" in out


def test_apply_bad_config(tmp_path, capsys):
    (tmp_path / "bad.yaml").write_text("apiVersion: v1\nkind: Pod\n")
    rc = main(["apply", "-f", str(tmp_path / "bad.yaml")])
    assert rc == 1
    assert "expected apiVersion simon/v1alpha1" in capsys.readouterr().err


def test_output_file(tmp_path):
    out_file = tmp_path / "report.txt"
    rc = main(["apply", "-f", os.path.join(REPO, "examples/config.yaml"),
               "--max-new-nodes", "2", "--output-file", str(out_file)])
    assert rc == 0
    assert "=== Nodes ===" in out_file.read_text()


def test_gen_doc(tmp_path):
    rc = main(["gen-doc", "--dir", str(tmp_path / "docs")])
    assert rc == 0
    files = os.listdir(tmp_path / "docs")
    assert "simon-tpu.md" in files and "simon-tpu_apply.md" in files


def test_gen_doc_writes_only_the_cli_tree(tmp_path):
    """gen-doc documents the simon-tpu command tree and nothing else:
    one page for the root and one per subcommand, and no page for a
    script outside the package."""
    from open_simulator_tpu.cli.main import build_parser

    rc = main(["gen-doc", "--dir", str(tmp_path / "docs")])
    assert rc == 0
    files = set(os.listdir(tmp_path / "docs"))
    [sub] = [a for a in build_parser()._subparsers._group_actions]
    assert files == {"simon-tpu.md"} | {
        f"simon-tpu_{name}.md" for name in sub.choices}


def test_apply_more_pods_sweep_answer(capsys):
    """The more-pods scale corpus (reference example/application/more_pods
    analog): the batched sweep must land on a stable minimum node count."""
    rc = main(["apply", "-f", os.path.join(REPO, "examples/morepods-config.yaml"),
               "--max-new-nodes", "16"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "requires 3 new node(s)" in out
