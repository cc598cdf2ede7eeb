"""Stage partitioning: layer-range manifests and per-stage param subsets.

Capability parity with the reference's stage table + splitter
(/root/reference/petals/inferd.yaml:1-24 — per-node name/stage/start_layer/
end_layer; /root/reference/split_model.py:76-108 — slicing a full model into
FirstStage/StageInner/LastStage torch modules). Redesigned: a stage is a
*pytree slice* of the stacked layer params plus optional embed / final-norm /
lm-head entries and a StageSpec of flags — no module class hierarchy, and the
same checkpoint format (flax msgpack) everywhere.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Dict, List, Optional

import yaml

from inferd_tpu.config import ModelConfig, get_config
from inferd_tpu.models import qwen3

Params = Dict[str, Any]


@dataclasses.dataclass(frozen=True)
class StageSpec:
    """One pipeline stage: a contiguous [start_layer, end_layer] (inclusive,
    matching the reference's yaml convention) slice of the decoder stack."""

    stage: int
    num_stages: int
    start_layer: int
    end_layer: int  # inclusive

    @property
    def is_first(self) -> bool:
        return self.stage == 0

    @property
    def is_last(self) -> bool:
        return self.stage == self.num_stages - 1

    @property
    def num_layers(self) -> int:
        return self.end_layer - self.start_layer + 1


@dataclasses.dataclass(frozen=True)
class NodeSpec:
    name: str
    stage: int
    start_layer: int
    end_layer: int


@dataclasses.dataclass
class Manifest:
    """Cluster topology: model + stage table (possibly with replicated
    stages, e.g. two nodes serving the same stage for DP load-balancing —
    reference inferd.yaml:16-24)."""

    model_name: str
    num_stages: int
    nodes: List[NodeSpec]

    @property
    def config(self) -> ModelConfig:
        return get_config(self.model_name)

    def stage_spec(self, stage: int) -> StageSpec:
        for n in self.nodes:
            if n.stage == stage:
                return StageSpec(stage, self.num_stages, n.start_layer, n.end_layer)
        raise KeyError(f"no node serves stage {stage}")

    def stage_specs(self) -> List[StageSpec]:
        return [self.stage_spec(s) for s in range(self.num_stages)]

    def node(self, name: str) -> NodeSpec:
        for n in self.nodes:
            if n.name == name:
                return n
        raise KeyError(f"no node named {name!r}")

    def validate(self, cfg: Optional[ModelConfig] = None) -> None:
        cfg = cfg or self.config
        specs = self.stage_specs()
        if specs[0].start_layer != 0:
            raise ValueError("stage 0 must start at layer 0")
        if specs[-1].end_layer != cfg.num_layers - 1:
            raise ValueError(
                f"last stage must end at layer {cfg.num_layers - 1}, got {specs[-1].end_layer}"
            )
        for a, b in zip(specs, specs[1:]):
            if b.start_layer != a.end_layer + 1:
                raise ValueError(
                    f"stages {a.stage}->{b.stage} not contiguous: "
                    f"{a.end_layer} then {b.start_layer}"
                )
        # replicas of a stage must agree on the layer range
        for n in self.nodes:
            s = self.stage_spec(n.stage)
            if (n.start_layer, n.end_layer) != (s.start_layer, s.end_layer):
                raise ValueError(
                    f"node {n.name} layer range differs from its stage {n.stage} range"
                )

    @staticmethod
    def from_yaml(path_or_text: str) -> "Manifest":
        if os.path.exists(path_or_text):
            with open(path_or_text) as f:
                data = yaml.safe_load(f)
        else:
            data = yaml.safe_load(path_or_text)
        nodes = [
            NodeSpec(
                name=n["name"],
                stage=int(n["stage"]),
                start_layer=int(n["start_layer"]),
                end_layer=int(n["end_layer"]),
            )
            for n in data["nodes"]
        ]
        return Manifest(
            model_name=data["model_name"],
            num_stages=int(data["stages_count"]),
            nodes=nodes,
        )

    def to_yaml(self) -> str:
        return yaml.safe_dump(
            {
                "model_name": self.model_name,
                "stages_count": self.num_stages,
                "nodes": [dataclasses.asdict(n) for n in self.nodes],
            },
            sort_keys=False,
        )

    @staticmethod
    def even_split(model_name: str, num_stages: int, replicas: Optional[List[int]] = None) -> "Manifest":
        """Even layer split into num_stages; replicas[s] nodes per stage."""
        cfg = get_config(model_name)
        replicas = replicas or [1] * num_stages
        per = cfg.num_layers // num_stages
        extra = cfg.num_layers % num_stages
        nodes, start = [], 0
        for s in range(num_stages):
            n_layers = per + (1 if s < extra else 0)
            end = start + n_layers - 1
            for r in range(replicas[s]):
                nodes.append(NodeSpec(f"node{s}_{r}" if replicas[s] > 1 else f"node{s}", s, start, end))
            start = end + 1
        return Manifest(model_name=model_name, num_stages=num_stages, nodes=nodes)


# ---------------------------------------------------------------------------
# Param subsetting + stage checkpoints
# ---------------------------------------------------------------------------


def extract_stage_params(full: Params, cfg: ModelConfig, spec: StageSpec) -> Params:
    """The param subset a stage needs: its layer slice, plus embed on the
    first stage and final-norm/lm-head on the last (reference
    split_model.py:92-102 semantics, as pytree slicing)."""
    groups = [g for g in ("dense_layers", "state_layers", "ffn_layers") if g in full]
    if groups:
        # more than one stack of layers (models/qwen3.layer_groups, or a
        # stack per kind of layer): kept whole, in the one stage that the
        # lane path serves
        if spec.num_stages != 1:
            raise ValueError(
                f"{cfg.name}: a model with {' and '.join(groups)} is served whole "
                f"(one stage), not split into {spec.num_stages}"
            )
        out: Params = {g: full[g] for g in groups + ["layers"]}
    else:
        out = {
            "layers": qwen3.slice_layers(full["layers"], spec.start_layer, spec.end_layer + 1)
        }
    if spec.is_first:
        out["embed"] = full["embed"]
    if spec.is_last:
        out["final_norm"] = full["final_norm"]
        if cfg.tie_word_embeddings:
            # tied head: last stage needs the embedding matrix too
            out["embed"] = full["embed"]
        else:
            out["lm_head"] = full["lm_head"]
    return out


def save_stage_checkpoint(path: str, stage_params: Params, spec: StageSpec, model_name: str) -> None:
    """Write one stage's params + metadata (flax msgpack — safe dense
    encoding, unlike the reference's pickle `torch.save` blobs, SURVEY B8)."""
    from flax import serialization

    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    meta = {
        "model_name": model_name,
        "stage": spec.stage,
        "num_stages": spec.num_stages,
        "start_layer": spec.start_layer,
        "end_layer": spec.end_layer,
    }
    with open(path, "wb") as f:
        f.write(serialization.to_bytes({"meta_json": json.dumps(meta), "params": stage_params}))


def load_stage_checkpoint(path: str) -> tuple[Params, StageSpec, str]:
    from flax import serialization

    with open(path, "rb") as f:
        blob = serialization.msgpack_restore(f.read())
    meta = json.loads(blob["meta_json"])
    spec = StageSpec(
        stage=int(meta["stage"]),
        num_stages=int(meta["num_stages"]),
        start_layer=int(meta["start_layer"]),
        end_layer=int(meta["end_layer"]),
    )
    return blob["params"], spec, meta["model_name"]


def stage_checkpoint_path(parts_dir: str, stage: int) -> str:
    return os.path.join(parts_dir, f"stage_{stage:03d}.msgpack")


def split_and_save(
    full: Params, cfg: ModelConfig, manifest: Manifest, parts_dir: str
) -> List[str]:
    """Split a full param pytree into per-STAGE checkpoints (not per-node:
    replicas share a file — fixing the reference's per-node duplication that
    made migration impossible, SURVEY B2)."""
    manifest.validate(cfg)
    paths = []
    for spec in manifest.stage_specs():
        sp = extract_stage_params(full, cfg, spec)
        path = stage_checkpoint_path(parts_dir, spec.stage)
        save_stage_checkpoint(path, sp, spec, manifest.model_name)
        paths.append(path)
    with open(os.path.join(parts_dir, "manifest.yaml"), "w") as f:
        f.write(manifest.to_yaml())
    return paths
