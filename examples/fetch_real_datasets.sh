#!/bin/bash
# One-command fetch + train on the reference's real corpora (GLUE SST-2,
# Criteo sample).  The build image has ZERO egress, so this script cannot
# succeed there.
# On any machine with network access:
#
#   bash examples/fetch_real_datasets.sh && \
#     python examples/finetune_bert_glue.py --data-dir datasets/glue --task sst2 && \
#     python examples/train_ctr.py --model wdl
#
# (finetune_bert_glue.py auto-uses datasets/glue/<task>/{train,dev}.tsv;
#  train_ctr.py auto-uses datasets/criteo/train.txt — both fall back to
#  synthetic only when the files are absent.)
set -euo pipefail
cd "$(dirname "$0")/.."

mkdir -p datasets/glue/sst2 datasets/criteo

# SST-2 (GLUE): the public zip from the GLUE benchmark hosting
curl -fL --retry 3 -o /tmp/sst2.zip \
  "https://dl.fbaipublicfiles.com/glue/data/SST-2.zip"
python - <<'EOF'
import zipfile
with zipfile.ZipFile("/tmp/sst2.zip") as z:
    for name in ("SST-2/train.tsv", "SST-2/dev.tsv"):
        dst = "datasets/glue/sst2/" + name.split("/")[-1]
        with z.open(name) as src, open(dst, "wb") as out:
            out.write(src.read())
print("SST-2 extracted to datasets/glue/sst2/")
EOF

# Criteo 1TB-sample day_0 is huge; the Kaggle display-ads sample is the
# reference's actual fixture (examples/ctr/tests download it the same way)
curl -fL --retry 3 -o /tmp/criteo_sample.tar.gz \
  "https://go.criteo.net/criteo-research-kaggle-display-advertising-challenge-dataset.tar.gz"
tar -xzf /tmp/criteo_sample.tar.gz -C datasets/criteo --wildcards "train.txt" \
  || tar -xzf /tmp/criteo_sample.tar.gz -C datasets/criteo
echo "Criteo extracted to datasets/criteo/"
