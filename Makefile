# Root lifecycle + smoke-generation Makefile.
#
# Role parity with the reference's two Makefiles:
#   - the root Makefile's non-interactive project smoke-gen + clean
#     (reference Makefile:5-19, `make cookiecutter` / `make clean`), here
#     driven by `ddlt new` instead of cookiecutter;
#   - the {{proj}}/Makefile control-plane lifecycle (build/run/bash/stop,
#     reference {{proj}}/Makefile:27-53), here `docker-build` / `docker-run` /
#     `docker-bash` / `docker-stop` over docker/Dockerfile.control.

PROJECT ?= smoke-test-project
IMAGE ?= ddlt-control
DATA_DIR ?= /data

.PHONY: install test test-fast lint perf-history obs-gate generate clean \
        bench-smoke bench scaling bench-tp bench-tier dryrun docker-build docker-run \
        docker-bash docker-stop

install:
	pip install -e .

test:
	python -m pytest tests/ -x -q

# Tier-1 flow: the hermetic observability gate runs first (attribution
# self-check + perf-trajectory gate, both seconds-cheap on CPU), then
# the fast test tier.
test-fast: obs-gate
	python -m pytest tests/ -x -q -m "not slow"

# Observability gate (obs/attrib.py + obs/history.py), hermetic: the
# attribution self-check builds its own tiny engines on the CPU backend
# and verifies program cost coverage + the HBM-ledger residual gates;
# the history gate re-reads every committed artifact as one metric
# timeline.  Non-zero exit on any gate failure.
obs-gate:
	python -m distributeddeeplearning_tpu.cli.main obs attrib --check
	python -m distributeddeeplearning_tpu.cli.main obs history --gate

# Static analysis (analysis/): AST hot-loop sync lint + jaxpr/HLO program
# audits.  Non-zero exit on any unwaived finding (the CLI pins a virtual
# CPU pod itself, so this works with no TPU attached).
lint:
	python -m distributeddeeplearning_tpu.cli.main lint

# Perf-trajectory gate (obs/history.py): every committed <KIND>_r{NN}.json
# parsed into one metric timeline; non-zero exit when a tracked metric
# regressed past its tolerance between the two newest revisions.
perf-history:
	python -m distributeddeeplearning_tpu.cli.main obs history --gate

# Smoke-generate a project non-interactively (reference Makefile:5-16).
generate:
	python -m distributeddeeplearning_tpu.cli.main new $(PROJECT) \
		--gcp-project smoke-project --gcs-bucket smoke-bucket
	@test -f $(PROJECT)/.env && test -f $(PROJECT)/Makefile \
		&& echo "generated $(PROJECT) OK"

clean:
	rm -rf $(PROJECT)

# Headline benchmark (tiny shapes — CI smoke; drop --small for real numbers).
bench-smoke:
	python bench.py --small

bench:
	python bench.py

# Allreduce scaling-efficiency sweep (BASELINE.json north-star #2).
scaling:
	python bench.py --devices 1,2,4,8 --small

# Tensor-parallel serving benchmark (TP_r{NN}.json): TP=1 vs TP=2 on a
# virtual pod, gated on bit-identical tokens, per-chip param HBM and the
# decode roofline.
bench-tp:
	python bench.py --tp 2

# Host-memory KV page tier benchmark (TIER_r{NN}.json): bit-identical
# spill/restore, prefix-hit rate and admitted-tokens/HBM-byte at 4-10x
# session oversubscription vs the no-tier baseline, decode parity when
# the working set fits in HBM.
bench-tier:
	python bench.py --tier

# Multi-chip sharding dry run on a virtual 8-device CPU pod (takes no chip).
dryrun:
	JAX_PLATFORMS=cpu XLA_FLAGS="$$XLA_FLAGS --xla_force_host_platform_device_count=8" python __graft_entry__.py 8

# ---- Control-plane container lifecycle ({{proj}}/Makefile:27-53 parity) ----

docker-build:
	docker build -t $(IMAGE) -f docker/Dockerfile.control .

docker-run:
	docker run -d --name $(IMAGE) \
		-v $(CURDIR):/workspace -v $(DATA_DIR):/data \
		-p 6006:6006 -p 9999:9999 \
		$(IMAGE) sleep infinity
	docker exec -it $(IMAGE) tmux new-session -s control

docker-bash:
	docker exec -it $(IMAGE) tmux attach-session -t control

docker-stop:
	docker rm -f $(IMAGE)
