#!/bin/sh
# Real two-container ssh end-to-end. Needs a docker
# daemon (absent in the TPU build environment — in-tree proxy coverage
# is tests/test_run.py::test_ssh_fanout_end_to_end_via_shim).
#
#   ./tools/ssh_e2e_compose.sh
#
# Brings up hosta+hostb (Dockerfile.test.cpu + sshd + shared keys), then
# drives `hvdrun -np 2 -H hosta:1,hostb:1` FROM hosta through the
# production ssh fan-out, ring NIC probe, and rendezvous; prints the
# per-rank allreduce results and exits nonzero on any failure.
set -eu
cd "$(dirname "$0")/.."

docker compose -f docker-compose.ssh.yml up -d --build hosta hostb
trap 'docker compose -f docker-compose.ssh.yml down -v' EXIT

# Wait for both sshds.
for h in hosta hostb; do
  for _ in $(seq 1 30); do
    if docker compose -f docker-compose.ssh.yml exec -T "$h" \
        sh -c 'pgrep -x sshd >/dev/null'; then break; fi
    sleep 2
  done
done

docker compose -f docker-compose.ssh.yml exec -T hosta sh -ec '
cat > /tmp/e2e_worker.py <<EOF
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import horovod_tpu as hvd
hvd.init()
import jax.numpy as jnp
s = hvd.allreduce(jnp.full((2,), float(hvd.rank() + 1)), op=hvd.Sum,
                  name="e2e")
print("SSHE2E", hvd.rank(), hvd.size(), float(np.asarray(s)[0]),
      flush=True)
hvd.shutdown()
EOF
# Both hosts need the worker at the same path (cwd is replicated by the
# fan-out, the script is shipped by path).
scp -o StrictHostKeyChecking=no /tmp/e2e_worker.py hostb:/tmp/e2e_worker.py
python -m horovod_tpu.run -np 2 -H hosta:1,hostb:1 --disable-cache \
    --output-dir /tmp/e2e_out python /tmp/e2e_worker.py
grep -h SSHE2E /tmp/e2e_out/rank.*.out
test "$(grep -hc "SSHE2E" /tmp/e2e_out/rank.*.out | paste -sd+ | bc)" = 2
'
echo "ssh e2e: OK"
