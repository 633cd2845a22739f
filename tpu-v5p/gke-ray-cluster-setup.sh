#!/usr/bin/env bash
# GKE + KubeRay bring-up for a TPU v5p slice — the second hardware
# generation dir, mirroring the reference's a3-ultra variant of the same
# runbook (reference: a3-ultra/gke-ray-cluster-setup.sh). v5p is the
# high-HBM generation (95 GB/chip): the target here is the Llama-3-70B
# GSPMD TP+DP fine-tune (BASELINE.json config 3), which needs tensor
# parallelism across chips — MESH_MODEL>1 in fine_tune_config.json.
#
# Topology 2x2x4 = 16 chips on ct5p-hightpu-4t hosts (4 chips each) →
# 4 hosts. v5p topologies are 3-D (AxBxC); host count = chips/4.
set -euo pipefail

export REGION=${REGION:-us-east5}
export ZONE=${ZONE:-us-east5-a}
export PROJECT_ID=${PROJECT_ID:?set PROJECT_ID}
export GKE_VERSION=${GKE_VERSION:-1.32.2-gke.1297002}
export CLUSTER_NAME=${CLUSTER_NAME:-tpu-v5p-ray}
export GSBUCKET=${GSBUCKET:-${CLUSTER_NAME}-artifacts}
export PROJECT_NUMBER=$(gcloud projects describe ${PROJECT_ID} --format="value(projectNumber)")
export NAMESPACE=${NAMESPACE:-default}
export KSA_NAME=${KSA_NAME:-tpu-ray}
export TPU_TOPOLOGY=${TPU_TOPOLOGY:-2x2x4}
export TPU_MACHINE_TYPE=${TPU_MACHINE_TYPE:-ct5p-hightpu-4t}
export TPU_ACCELERATOR=${TPU_ACCELERATOR:-tpu-v5p-slice}
export NUM_HOSTS=${NUM_HOSTS:-4}
export CHIPS_PER_HOST=${CHIPS_PER_HOST:-4}
export HF_TOKEN=${HF_TOKEN:-}

gcloud container clusters create ${CLUSTER_NAME} \
    --region=${REGION} \
    --node-locations=${ZONE} \
    --cluster-version=${GKE_VERSION} \
    --machine-type=n2-standard-8 \
    --num-nodes=1 \
    --enable-ray-cluster-logging \
    --enable-ray-cluster-monitoring \
    --workload-pool=${PROJECT_ID}.svc.id.goog \
    --addons=RayOperator,GcsFuseCsiDriver

gcloud container node-pools create tpu-v5p-slice \
    --cluster=${CLUSTER_NAME} \
    --project=${PROJECT_ID} \
    --region=${REGION} \
    --node-locations=${ZONE} \
    --node-version=${GKE_VERSION} \
    --machine-type=${TPU_MACHINE_TYPE} \
    --tpu-topology=${TPU_TOPOLOGY} \
    --num-nodes=${NUM_HOSTS}

python -m venv myenv && source myenv/bin/activate
pip install -U "ray[data,train,tune,serve]"

gcloud storage buckets create gs://${GSBUCKET} \
    --uniform-bucket-level-access \
    --location=${REGION} \
    --enable-hierarchical-namespace

kubectl create serviceaccount ${KSA_NAME}
gcloud storage buckets add-iam-policy-binding gs://${GSBUCKET} \
  --member "principal://iam.googleapis.com/projects/${PROJECT_NUMBER}/locations/global/workloadIdentityPools/${PROJECT_ID}.svc.id.goog/subject/ns/${NAMESPACE}/sa/${KSA_NAME}" \
  --role "roles/storage.objectUser"

kubectl create secret generic hf-secret --from-literal=HF_TOKEN=${HF_TOKEN}

envsubst < tpu-v5p/ray-cluster-config.yaml | kubectl apply -f -

kubectl wait --for=condition=Ready pod \
  --selector=ray.io/node-type=head,ray.io/cluster=tpu-raycluster \
  --timeout=600s
export HEAD_POD=$(kubectl get pods --selector=ray.io/node-type=head,ray.io/cluster=tpu-raycluster -o jsonpath='{.items[0].metadata.name}')
echo "Head pod: $HEAD_POD"
kubectl port-forward "$HEAD_POD" 8265:8265 &
sleep 5  # let the forward establish before submitting

# 70B fine-tune: same entry script as v5e with the 70B config file,
# which sets MESH_MODEL=4 (tensor parallel across chips) + fsdp.
ray job submit --address http://localhost:8265 --runtime-env-json='{
    "working_dir": ".",
    "pip": [
        "jax[tpu]==0.6.0",
        "flax",
        "optax",
        "orbax-checkpoint",
        "datasets==3.6.0",
        "transformers==4.50.0",
        "safetensors"
    ],
    "env_vars": {
        "NUM_HOSTS": "'"$NUM_HOSTS"'",
        "CHIPS_PER_HOST": "'"$CHIPS_PER_HOST"'",
        "FINE_TUNE_CONFIG": "ray-jobs/fine_tune_config_70b.json"
    }
}' -- python ray-jobs/fine_tune_llama_ray.py
# (HF_TOKEN reaches the workers from the hf-secret via the pod spec.)
