"""jaxserver entrypoint: `python -m kfserving_tpu.predictors.jaxserver`.

Args mirror the reference model-server convention (`--model_name
--model_dir --http_port [--workers]`, reference
pkg/apis/serving/v1beta1/predictor_sklearn.go:77-96 builds exactly these)
plus the reference agent's flags, served in-process (reference
cmd/agent/main.go:32-55): payload logging (--log_url/--log_mode), the
multi-model puller (--config_dir), and the TPU batching knobs.
"""

import argparse
import logging

from kfserving_tpu import startup
from kfserving_tpu.engine.compile_cache import enable as enable_compile_cache
from kfserving_tpu.predictors.jax_model import JaxModel
from kfserving_tpu.predictors.jaxserver.repository import JaxModelRepository
from kfserving_tpu.server.app import ModelServer, parser as server_parser

logging.basicConfig(level=logging.INFO)

parser = argparse.ArgumentParser(parents=[server_parser])
parser.add_argument("--model_name", default="model",
                    help="name under which the model is served")
parser.add_argument("--model_dir", required=True,
                    help="model artifact URI (local path, gs://, s3://...) "
                         "or, with --multi_model, the models root dir")
parser.add_argument("--multi_model", action="store_true",
                    help="serve a repository of models loaded on demand "
                         "via /v2/repository/models/{name}/load")
parser.add_argument("--config_dir", default=None,
                    help="model-config file/dir to watch for multi-model "
                         "serving (agent --config-dir equivalent)")
parser.add_argument("--log_url", default=None,
                    help="CloudEvents sink for payload logging "
                         "(agent --log-url equivalent)")
parser.add_argument("--log_mode", default="all",
                    choices=["all", "request", "response"])
parser.add_argument("--source_uri", default="",
                    help="CloudEvents source attribute")


def build_server(args) -> ModelServer:
    cc = getattr(args, "container_concurrency", 0)
    grpc_port = getattr(args, "grpc_port", None)
    multi_model = args.multi_model or args.config_dir
    if multi_model:
        repo = JaxModelRepository(models_dir=args.model_dir)
        server = ModelServer(http_port=args.http_port,
                             registered_models=repo,
                             container_concurrency=cc,
                             grpc_port=grpc_port)
    else:
        server = ModelServer(http_port=args.http_port,
                             container_concurrency=cc,
                             grpc_port=grpc_port)

    if args.config_dir:
        import asyncio

        from kfserving_tpu.agent import Downloader, ModelConfigWatcher, Puller

        events: asyncio.Queue = asyncio.Queue()
        watcher = ModelConfigWatcher(args.config_dir, events=events)
        puller = Puller(server.repository,
                        Downloader(args.model_dir), events=events)
        server.services += [watcher, puller]

    if args.log_url:
        from kfserving_tpu.agent import RequestLogger

        request_logger = RequestLogger(
            args.log_url, source_uri=args.source_uri,
            log_mode=args.log_mode)
        request_logger.attach(server)
        server.services.append(request_logger)
    return server


if __name__ == "__main__":
    import os

    args, _ = parser.parse_known_args()
    enable_compile_cache()
    server = build_server(args)
    if args.multi_model or args.config_dir:
        startup.report_device()
        server.start([])
    else:
        model = JaxModel(args.model_name, args.model_dir)

        def load():
            startup.report_device()  # first touch of the device
            model.load()
            return model

        if os.environ.get("KFS_STANDBY"):
            # Recycle fast-swap: imports and server setup are done, but
            # the model load (device init + compile) waits for the
            # orchestrator to POST /standby/activate once the
            # predecessor releases the chip (subprocess_orchestrator
            # recycle path).
            server.standby_model(load)
            server.start([])
        else:
            server.start([load()])
