# Developer entry points (reference parity: Taskfile.yml).

.PHONY: generate check test test-fast chip-smoke serve gateway lint graftlint typecheck

generate:  ## regenerate docs/env examples from openapi.yaml + drift check
	python -m inference_gateway_tpu.codegen

check:     ## spec<->code drift guards only
	python -m inference_gateway_tpu.codegen -type Check

graftlint: ## project-invariant static analysis (docs/static-analysis.md)
	python -m graftlint inference_gateway_tpu

lint: graftlint check  ## graftlint + spec<->code drift guards, one command

typecheck: ## mypy --strict over the typed core (module list: pyproject [tool.mypy])
	@if python -c "import mypy" 2>/dev/null; then \
		python -m mypy; \
	else \
		echo "mypy not installed in this environment; skipping (the typed-core module list lives in pyproject.toml [tool.mypy])"; \
	fi

test:      ## full suite on a virtual 8-device CPU mesh
	python -m pytest tests/ -q

# Exclusion list, not inclusion: a NEW test file runs in the fast tier
# by default (coverage can't silently drop); add it here only if it
# builds engines/models.
SLOW_TESTS := test_checkpoint test_chunked_prefill test_distributed \
  test_engine test_flash_attention test_gateway_e2e test_gemma test_graft_entry \
  test_llama_numerics test_metrics_push_loop test_mistral test_mixtral \
  test_moe_paged_quant test_moe_serving test_multihost test_multimodal \
  test_paged_attention test_paged_dispatch test_paged_sharded \
  test_pipeline test_pipelined_decode test_pp_serving test_prefix_cache \
  test_profiles test_quant test_qwen2 test_race_discipline \
  test_ring_attention test_ring_serving test_sampling_features \
  test_scheduler_resilience test_sharding test_sidecar_server \
  test_spec_ngram test_speculative test_structured_e2e test_vision

test-fast: ## gateway/protocol tier only (~2 min) — no engine builds
	python -m pytest tests/ -q $(foreach t,$(SLOW_TESTS),--ignore=tests/$(t).py)

chip-smoke:  ## the main path on one TPU chip (fails without one)
	python chip_smoke.py

serve:     ## run the TPU sidecar (random weights unless --checkpoint/model path)
	python -m inference_gateway_tpu.serving --model tinyllama-1.1b --port 8000

gateway:   ## run the gateway
	python -m inference_gateway_tpu.main
